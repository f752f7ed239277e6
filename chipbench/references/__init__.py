"""Plain references, one module per kind of block; a configuration names its
reference in ``"reference"``.

Each has ``weights(doc, seed) -> params`` (the cell's seeded weights, made by
the benchmark's own copy of the initialiser) and ``forward(doc, params,
token_ids, positions=None) -> float32 [rows, V]``: the published forward pass
over one whole sequence, plain ``jax.numpy`` at float32 and
``Precision.HIGHEST``, importing nothing of the program. ``chipbench/parity.py``
runs it over what the window served and decides ``correct`` by it. Where a
configuration holds a chip's share of a layer (experts, vocabulary rows), the
reference is given the same share (README, "Adding things").
"""

from chipbench import lookup


def load(name: str):
    return lookup.load_module("references", name)
