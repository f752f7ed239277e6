"""How late the load generator sent: send time - due time, 95th percentile."""
from chipbench import arith


def read(ctx):
    late = [r["sent"] - r["due"] for r in ctx["records"] if r["kind"] == "open" and r["sent"]]
    return arith.pctl(late, 95) * 1000.0 if late else None
