"""Maps from a configuration's file to the program's ``ModelConfig``, one
module per kind of block; a configuration names its map in ``"model_map"``.

Each has ``fields(doc) -> dict`` of ``ModelConfig`` keyword arguments. A new
kind of block is the program first (its ``ModelConfig`` fields and its block),
then a map here that passes the published keys on: nothing in ``run.py`` or
``launch_worker.py`` names a model key. There is no default map.
"""

from __future__ import annotations

import dataclasses

from chipbench import lookup


def load(name: str):
    return lookup.load_module("model_maps", name)


def fields(doc: dict) -> dict:
    """The ``ModelConfig`` keyword arguments the configuration's map gives.
    ``lookup.Missing`` where the file names no map or one that is not there."""
    name = doc.get("model_map")
    if not name:
        raise lookup.Missing(f"configuration {doc.get('name')!r} has no \"model_map\" key; the maps "
                             f"there are: {lookup.names('model_maps', '.py')}")
    return dict(load(name).fields(doc))


def model_config(doc: dict):
    """The program's ``ModelConfig`` for a configuration's document. A field
    the program does not have ends in a message that names it beside the
    fields there are: what the program lacks for this block."""
    from dynamo_tpu.engine.config import ModelConfig

    got = fields(doc)
    have = [f.name for f in dataclasses.fields(ModelConfig)]
    unknown = sorted(set(got) - set(have))
    if unknown:
        raise lookup.Missing(f"model map {doc['model_map']!r} gives {unknown}, which the program's "
                             f"ModelConfig does not have; its fields are {have}")
    return ModelConfig(**got)
