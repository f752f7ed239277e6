"""The seam between the scheduler and a block's second cache (engine/side.py),
held with a kind that only records: the events the scheduler calls, their
order in a sequence's residence, one a dispatch, release once a residence."""

import ast
import asyncio
import os
import re

import numpy as np
import pytest

from dynamo_tpu.block_manager.pool import NoFreeBlocksError
from dynamo_tpu.engine import model as M
from dynamo_tpu.engine.config import EngineArgs, ModelConfig
from dynamo_tpu.engine.engine import TpuEngine
from dynamo_tpu.engine.side import SideCache
from dynamo_tpu.llm.protocols import PreprocessedRequest
from dynamo_tpu.runtime.engine import Context
from dynamo_tpu.runtime.metrics import MetricsRegistry
from tests.engine_waves import one_wave

ENGINE_PY = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..", "dynamo_tpu", "engine", "engine.py")


class Recorder(SideCache):
    """Every event as ``(letter, sequence tags)``; a sequence's tag is its
    first prompt token. ``refuse`` holds the tags whose next ``admit`` finds
    no block; ``operand`` makes it answer a dispatch with one."""

    def __init__(self, operand: bool = False):
        self.events: list[tuple[str, tuple]] = []
        self.refuse: set[int] = set()
        self.operand = operand
        self.bound = self.fed = 0

    def _note(self, letter: str, *seqs) -> None:
        self.events.append((letter, tuple(s.tokens[0] for s in seqs)))

    def max_hit(self, hashes):
        self.events.append(("M", ()))
        return None, [len(hashes)]

    def admit(self, seq, rec, hashes, n_hit):
        assert seq.side is None and rec == [len(hashes)]
        if seq.tokens[0] in self.refuse:
            self.refuse.discard(seq.tokens[0])
            self._note("X", seq)
            raise NoFreeBlocksError("the recorder has no block")
        self._note("A", seq)
        seq.side = rec

    def prefill_rows(self, rows, Bp, W):
        assert all(seq.side is not None and 0 <= start < end <= len(seq.tokens) for seq, start, end in rows)
        self._note("P", *(seq for seq, _, _ in rows))
        return np.full((Bp, 2), 7, np.int32) if self.operand else None

    def end_wave(self):
        self.events.append(("W", ()))

    def registered(self, seq, index, block):
        assert seq.side is not None and index == seq.registered_blocks
        self._note("G", seq)

    def cover_decode(self, seq, first_pos, last_pos):
        assert seq.side is not None and first_pos <= last_pos
        self._note("C", seq)
        return True

    def decode_rows(self, batch, pos0, B, K, W):
        assert all(seq.side is not None for seq in batch) and len(pos0) == len(batch) <= B
        self._note("D", *batch)
        return np.full((B, 3), 7, np.int32) if self.operand else None

    def release(self, seq):
        assert seq.side is not None
        self._note("R", seq)
        seq.side = None

    def bind_metrics(self, gauges):
        self.bound += 1

    def feed(self, gauges, feed):
        self.fed += 1


def request(tag: int, n: int, max_tokens: int) -> PreprocessedRequest:
    rest = np.random.RandomState(tag).randint(10, ModelConfig().vocab_size, n - 1)
    req = PreprocessedRequest(model="t", token_ids=[tag] + [int(t) for t in rest])
    req.sampling.temperature = 0.0
    req.sampling.seed = 0
    req.stop.max_tokens, req.stop.ignore_eos = max_tokens, True
    return req


async def tokens(engine, req) -> list[int]:
    return [t async for o in engine.generate(req, Context()) for t in o.get("token_ids", [])]


def recording(engine: TpuEngine, kind: Recorder) -> None:
    """``kind`` in the seam of ``engine``, and the runner's dispatches in the
    same record (lower case), each with the operand it was handed taken off."""
    engine.side = kind
    for name, letter in (("prefill_batch", "p"), ("prefill_chunk", "p"), ("multi_decode", "d"), ("decode_step", "d")):
        def dispatch(*a, _run=getattr(engine._runner, name), _letter=letter, **kw):
            state = kw.pop("state", None)
            kind.events.append((_letter, () if state is None else (state.shape, int(state.min()), int(state.max()))))
            return _run(*a, **kw)
        setattr(engine._runner, name, dispatch)


@pytest.mark.parametrize("case", ["finishes", "is_preempted_and_returns", "finds_no_block_at_admission"])
def test_the_scheduler_calls_the_kind_at_its_events_and_nowhere_else(case):
    """test-tiny has no second cache: a recording kind in ``engine.side`` sees
    what a real one would. A residence is admit, its prefill rows, cover before
    every decode dispatch it rides, release, once each; ``max_hit`` stands
    before every admit, refused or not; every dispatch of the runner has exactly
    one event of its kind before it and gets ``state=`` only where the kind
    answered with an operand."""
    small = case == "is_preempted_and_returns"
    args = EngineArgs(model=ModelConfig(), block_size=4, num_kv_blocks=20 if small else 64, max_num_seqs=4,
                      max_model_len=128, max_prefill_tokens=16, dtype="float32")
    kind = Recorder(operand=case == "finishes")

    async def go():
        engine = TpuEngine(args)
        recording(engine, kind)
        engine.bind_metrics(MetricsRegistry())
        await engine.start()
        try:
            if case == "finishes":  # 40 tokens: three chunks, then a follow-up that hits its pages
                out = [await tokens(engine, request(11, 40, 12)), await tokens(engine, request(11, 40, 12))]
            elif small:  # three of 20 + 30 tokens want 39 blocks of 19
                out = await asyncio.gather(*(tokens(engine, request(t, 20, 30)) for t in (11, 12, 13)))
            else:  # one wave: the first is allocated, the second refused once and taken by the next step
                kind.refuse = {12}
                out = await one_wave(engine, [tokens(engine, request(t, 20, 12)) for t in (11, 12)])
            free = await engine.run_on_engine_thread(lambda: (engine.pool.num_active, engine.prefill_waves))
            return out, sum(engine.total_preemptions_by.values()), free
        finally:
            await engine.stop()

    out, preempted, (active, waves) = asyncio.run(go())
    assert [len(o) for o in out] == [30 if small else 12] * len(out) and active == 0
    assert kind.bound == 1 and kind.fed > 0
    events = kind.events
    tags = sorted({t for letter, ts in events if letter in "XAPGCDR" for t in ts})
    assert tags == ([11] if case == "finishes" else [11, 12, 13] if small else [11, 12])

    # One residence after another, each in order, release once each.
    for tag in tags:
        own = "".join(letter for letter, ts in events if tag in ts and letter in "XAPCDR")
        assert re.fullmatch(r"(X*AP+(C+D)*C*R)+", own), (tag, own)
        assert own.count("A") == own.count("R")
    whole = "".join(letter for letter, _ in events)
    admits = whole.count("A")
    assert admits == {"finishes": 2, "finds_no_block_at_admission": 2}.get(case, 3 + preempted)
    assert (preempted > 0) == small and whole.count("X") == (case == "finds_no_block_at_admission")
    # max_hit before every admit (and alone where the page pool had no room: a small pool's only);
    # a wave's end once a wave; a registration only inside a residence (asserted there).
    assert not re.search(r"(?<!M)[AX]", whole)
    assert (whole.count("M") >= admits + whole.count("X")) if small else (whole.count("M") == admits + whole.count("X"))
    assert whole.count("W") == waves and "G" in whole
    # Every dispatch of the runner: exactly one event of its kind since the dispatch before it.
    assert re.sub(r"[^PDpd]", "", whole).replace("Pp", "").replace("Dd", "") == ""
    handed = [ts for letter, ts in events if letter in "pd"]
    if case == "finishes":  # the operand as it was answered; a chunk gets row 0 of its one row
        assert all(ts and ts[1:] == (7, 7) for ts in handed)
        assert {ts[0] for letter, ts in events if letter == "p"} == {(2,), (1, 2)}
        assert {len(ts[0]) for letter, ts in events if letter == "d"} == {2}
    else:
        assert handed and not any(handed)


def _engine_source() -> ast.Module:
    with open(ENGINE_PY) as f:
        return ast.parse(f.read())


def test_the_scheduler_knows_no_block_by_name():
    """engine/engine.py: no block's name as a string constant (docstrings and
    help texts apart: a name inside a longer text is prose) and no comparison
    of ``cfg.block``; no attribute of a second cache's (``state_*``,
    ``window_*``); ``ops.dsa`` is the kind's to import."""
    tree = _engine_source()
    names = set(M.BLOCK_MODULES[1:])
    constants = {n.value for n in ast.walk(tree) if isinstance(n, ast.Constant) and isinstance(n.value, str)}
    assert not constants & names
    compared = [n for n in ast.walk(tree) if isinstance(n, ast.Compare)
                and any(isinstance(x, ast.Attribute) and x.attr == "block" for x in ast.walk(n))]
    assert not compared
    attrs = {n.attr for n in ast.walk(tree) if isinstance(n, ast.Attribute)}
    assert not {a for a in attrs if a.startswith(("state_", "window_"))}
    assert "dsa" not in {a.name for n in ast.walk(tree) if isinstance(n, ast.ImportFrom) for a in n.names}
