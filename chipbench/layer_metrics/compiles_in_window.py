"""Programs JAX built or took from its persistent cache inside the window,
over all workers: either stalls the request that needed the shape."""


def read(ctx):
    total, seen = 0, False
    for r in range(ctx["replicas"]):
        a, b = ctx["stats"].get(f"{r}.0"), ctx["stats"].get(f"{r}.1")
        if a and b:
            total, seen = total + b["compile_requests"] - a["compile_requests"], True
    return float(total) if seen else None
