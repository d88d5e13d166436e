"""Server entrypoint — ``python -m ksql_tpu.server``.

KsqlServerMain.java:46 analog: parse flags/properties, build the engine,
serve.  ``--queries-file`` (or ksql.queries.file in --properties) starts
the node headless (StandaloneExecutor.java:73): the SQL file defines the
queries and the REST API serves reads only.
"""

from __future__ import annotations

import argparse
import json
import signal
import sys
import threading


def main(argv=None) -> int:
    p = argparse.ArgumentParser(prog="ksql-server")
    p.add_argument("--host", default="127.0.0.1")
    p.add_argument("--port", type=int, default=8088)
    p.add_argument("--properties", help="JSON file of ksql.* config keys")
    p.add_argument("--queries-file",
                   help="headless mode: run this SQL file, serve reads only")
    p.add_argument("--command-log", help="command-log WAL path")
    p.add_argument("--peers", nargs="*", default=None,
                   help="peer server URLs (heartbeats + pull forwarding)")
    args = p.parse_args(argv)

    props = {}
    if args.properties:
        with open(args.properties) as f:
            props.update(json.load(f))
    if args.queries_file:
        props["ksql.queries.file"] = args.queries_file

    from ksql_tpu.runtime import compile_cache

    compile_cache.place()

    from ksql_tpu.common.config import KsqlConfig
    from ksql_tpu.engine.engine import KsqlEngine
    from ksql_tpu.server.rest import KsqlServer

    engine = KsqlEngine(KsqlConfig(props))
    server = KsqlServer(
        engine=engine, host=args.host, port=args.port,
        command_log_path=args.command_log, peers=args.peers,
    )
    server.start()
    mode = "headless" if server.headless else "interactive"
    print(f"ksql server listening on {server.url} ({mode})", flush=True)
    stop = threading.Event()
    signal.signal(signal.SIGINT, lambda *_: stop.set())
    signal.signal(signal.SIGTERM, lambda *_: stop.set())
    stop.wait()
    server.stop()
    return 0


if __name__ == "__main__":
    sys.exit(main())
