"""Test helper: requests that reach the engine as ONE admission wave.

Which requests share an admission wave decides which prefills go out packed
(engine.py:_dispatch_prefills), and a packed row's logits differ in their
last bits from the same row's alone. A test that holds streams byte for byte
across engines, or counts a wave's dispatches, fixes the wave with this
instead of leaving it to the machine's timing."""

import asyncio
import threading


async def one_wave(engine, streams) -> list:
    """Await ``streams`` (coroutines that each consume one
    ``engine.generate``) with the scheduler thread held in a job until every
    one of them has run to its first wait, which is past its enqueue: the
    scheduler's next step admits them together, whatever the machine's load."""
    started, release = threading.Event(), threading.Event()
    hold = asyncio.ensure_future(
        engine.run_on_engine_thread(lambda: (started.set(), release.wait(30))))
    try:
        await asyncio.to_thread(started.wait, 30)
        tasks = [asyncio.ensure_future(s) for s in streams]
        await asyncio.sleep(0)  # each task runs up to its first wait
        release.set()
        return list(await asyncio.gather(*tasks))
    finally:
        release.set()
        await hold
