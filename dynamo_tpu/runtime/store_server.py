"""CLI: run a standalone control-plane store server.

Usage: ``python -m dynamo_tpu.runtime.store_server [--host H] [--port P]``

One per cluster (analogue of the reference's etcd; SURVEY.md §1 layer 0).
"""

from __future__ import annotations

import argparse
import asyncio
import contextlib
import signal

from dynamo_tpu.runtime.store_net import StoreServer


async def _main(host: str, port: int) -> None:
    server = await StoreServer(host, port).start()
    print(f"dynamo_tpu store server: {server.url}", flush=True)
    # Same contract as the worker and the frontend: SIGTERM exits 0. It
    # exits at once: returning lets asyncio.run cancel the per-connection
    # handlers, which drops the clients. Waiting for them to leave first
    # (Server.wait_closed) would hold the store up for as long as any
    # client lives.
    stop = asyncio.Event()
    loop = asyncio.get_running_loop()
    for sig in (signal.SIGINT, signal.SIGTERM):
        with contextlib.suppress(NotImplementedError):
            loop.add_signal_handler(sig, stop.set)
    await stop.wait()


def main() -> None:
    parser = argparse.ArgumentParser(description="dynamo_tpu control-plane store server")
    parser.add_argument("--host", default="0.0.0.0")
    parser.add_argument("--port", type=int, default=3280)
    args = parser.parse_args()
    try:
        asyncio.run(_main(args.host, args.port))
    except KeyboardInterrupt:
        pass


if __name__ == "__main__":
    main()
