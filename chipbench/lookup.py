"""Where the benchmark finds a file by the name a configuration or
``BENCHMARK.json`` gives it: ``<dir>/<kind>/<name><ext>`` in each directory of
``CHIPBENCH_PATH`` (``os.pathsep`` between them; the driver never sets it),
then in ``chipbench/`` itself. A later PR adds files beside the ones here; a
test puts its own in a temporary directory and names that in the variable."""

from __future__ import annotations

import importlib.util
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))


class Missing(LookupError):
    """No file of that name; the message says which names there are."""


def search_dirs(kind: str) -> list[str]:
    extra = [d for d in os.environ.get("CHIPBENCH_PATH", "").split(os.pathsep) if d]
    return [os.path.join(d, kind) for d in [*extra, HERE]]


def names(kind: str, ext: str) -> list[str]:
    out = set()
    for d in search_dirs(kind):
        if os.path.isdir(d):
            out |= {f[: -len(ext)] for f in os.listdir(d) if f.endswith(ext) and not f.startswith("_")}
    return sorted(out)


def find(kind: str, name: str, ext: str) -> str:
    for d in search_dirs(kind):
        path = os.path.join(d, str(name) + ext)
        if os.path.isfile(path):
            return path
    raise Missing(f"no {kind}/{name}{ext}; there are {names(kind, ext)}")


def load_module(kind: str, name: str):
    """The module ``<kind>/<name>.py``, imported from where it was found."""
    path = find(kind, name, ".py")
    mod_name = f"chipbench_{kind}_{name}".replace("-", "_").replace(".", "_")
    if mod_name in sys.modules and getattr(sys.modules[mod_name], "__file__", None) == path:
        return sys.modules[mod_name]
    spec = importlib.util.spec_from_file_location(mod_name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[mod_name] = mod
    spec.loader.exec_module(mod)
    return mod
