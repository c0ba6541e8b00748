"""`python -m paddle_tpu.inference.serve` — run the streaming HTTP
serving front-end over a saved model (ISSUE 12).

Artifacts:
  * `<prefix>.pdparams` (+ `<prefix>.config.json` sidecar, or --config)
    — a `jit.save` / `gateway.save_for_serving` causal LM: serves
    `POST /v1/generate` token streams through the continuous-batching
    engine (prefix cache, SLO scheduling and admission control
    included).
  * `<prefix>.pdmodel` + `<prefix>.pdiparams` — a
    `static.save_inference_model` pair: loaded HEADLESS (no Executor)
    and served at `POST /v1/infer`.
  Both may sit at one prefix; each endpoint appears when its artifact
  does.

Signals: SIGTERM/SIGINT start a graceful drain — /healthz flips to 503
with Retry-After (load balancers stop routing), new submits get 503,
in-flight streams finish (bounded by --drain-timeout), then the process
exits. A second signal exits immediately.

Example:
  JAX_PLATFORMS=cpu python -m paddle_tpu.inference.serve \\
      --model /tmp/m --port 8008 --max-queue-tokens 1024
  curl -N localhost:8008/v1/generate \\
      -d '{"prompt": [3, 5, 7], "max_new_tokens": 8}'
"""
from __future__ import annotations

import argparse
import os
import signal
import sys
import threading


def _build_parser():
    p = argparse.ArgumentParser(
        prog="python -m paddle_tpu.inference.serve",
        description="streaming HTTP gateway over the continuous-"
                    "batching engine")
    p.add_argument("--model", required=True,
                   help="artifact path prefix (jit.save / "
                        "save_inference_model)")
    p.add_argument("--config", default=None,
                   help="LlamaConfig preset name (llama_tiny...) or "
                        "JSON file; default: <prefix>.config.json")
    p.add_argument("--host", default="127.0.0.1")
    p.add_argument("--port", type=int, default=8000,
                   help="0 picks a free port (printed at startup)")
    p.add_argument("--max-batch", type=int, default=4)
    p.add_argument("--max-seq", type=int, default=256)
    p.add_argument("--page-size", type=int, default=16)
    p.add_argument("--total-pages", type=int, default=None)
    p.add_argument("--max-chunk-tokens", type=int, default=64)
    p.add_argument("--max-queue-tokens", type=int, default=None,
                   help="queue bound behind the 429 backpressure path "
                        "(default: 8 * max_seq)")
    p.add_argument("--quantize", choices=("int8",), default=None)
    p.add_argument("--max-draft-tokens", type=int, default=None,
                   help="self-speculative draft-length cap (default "
                        "FLAGS_speculative_draft_tokens; 0 disables "
                        "drafting for this engine)")
    p.add_argument("--keepalive-s", type=float, default=0.5,
                   help="SSE keepalive interval (doubles as the "
                        "client-disconnect probe)")
    p.add_argument("--drain-timeout", type=float, default=30.0)
    p.add_argument("--metrics-port", type=int, default=0,
                   help="also serve the standalone observability "
                        "/metrics endpoint (FLAGS_metrics_port)")
    return p


def main(argv=None):
    args = _build_parser().parse_args(argv)
    from .. import observability as obs
    from ..framework import core as _core
    from ..framework.compile_cache import use_compile_cache
    from . import gateway as gw

    use_compile_cache()
    obs.enable(True)
    if args.metrics_port:
        _core.set_flags({"FLAGS_metrics_port": args.metrics_port})

    runner = None
    static_model = None
    if os.path.exists(args.model + ".pdparams"):
        model = gw.load_generation_model(args.model, config=args.config)
        engine = gw.build_engine(
            model, max_batch=args.max_batch, max_seq=args.max_seq,
            page_size=args.page_size, total_pages=args.total_pages,
            max_chunk_tokens=args.max_chunk_tokens,
            max_queue_tokens=args.max_queue_tokens,
            max_draft_tokens=args.max_draft_tokens,
            quantize=args.quantize)
        runner = gw.EngineRunner(engine)
    if os.path.exists(args.model + ".pdiparams") and \
            os.path.exists(args.model + ".pdmodel"):
        static_model = gw.load_static_model(args.model)
    if runner is None and static_model is None:
        print(f"no servable artifact at {args.model!r} (need .pdparams "
              f"or .pdmodel/.pdiparams)", file=sys.stderr)
        return 2

    g = gw.ServingGateway(runner=runner, static_model=static_model,
                          host=args.host, port=args.port,
                          keepalive_s=args.keepalive_s)
    port = g.start()
    endpoints = ["GET /healthz", "GET /metrics"]
    if runner is not None:
        endpoints.insert(0, "POST /v1/generate")
    if static_model is not None:
        endpoints.insert(1, "POST /v1/infer")
    # a fleet-supervised replica announces its identity (the supervisor
    # parses the port from this line; the identity also rides /healthz
    # so the router can verify a relaunched incarnation)
    ident = ""
    rid = os.environ.get("PADDLE_TRAINER_ID")
    if rid is not None:
        ident = (f"  [replica {rid} "
                 f"inc {os.environ.get('PADDLE_INCARNATION', '0')}]")
    print(f"serving on http://{args.host}:{port}  "
          f"({', '.join(endpoints)}){ident}", flush=True)

    stop = threading.Event()

    def _drain_then_stop():
        g.drain(timeout=args.drain_timeout)
        stop.set()

    def _on_signal(signum, frame):
        if g.draining:                  # second signal: leave now
            stop.set()
            return
        print(f"signal {signum}: draining "
              f"(timeout {args.drain_timeout}s)", flush=True)
        # one-shot signal-driven drain; main's stop.wait() is the
        # join path  # graft-lint: disable=thread-hygiene
        threading.Thread(target=_drain_then_stop, daemon=True,
                         name="paddle-serve-drain").start()

    signal.signal(signal.SIGTERM, _on_signal)
    signal.signal(signal.SIGINT, _on_signal)
    try:
        stop.wait()
    finally:
        g.stop()
    print("drained, bye", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
