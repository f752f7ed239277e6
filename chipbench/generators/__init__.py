"""Traffic generators, one module per ``kind``; found by the name in a mix file.

Each has ``generate(params, seed, seconds, vocab_size) -> plan``. A plan is a
dict of plain lists and numbers (``run.py`` hashes its JSON):

    mode          "open"  : ``requests`` = [{due, prompt, max_tokens}], sent on
                            schedule whatever the server does
                  "closed": ``clients`` = [{prefill, turns: [{think_s, base,
                            new, max_tokens}]}] and ``system_prompts``; a
                            client sends its next turn when the last answered
    shares_prefix whether warm-up has to cover prefills behind a cached prefix
    prompt_max    the longest prompt, so warm-up skips buckets nothing reaches

The same seed gives the same plan. Every seed gets the same sizes, arrival
gaps and think times (drawn from the mix's ``shape_seed``) in the same order,
with other token ids: runs differ by the data and by nothing else, so a
tail is the tail of one sample path and repeats to a percent or two.
"""

import importlib


def load(kind: str):
    return importlib.import_module(f"chipbench.generators.{kind}")
