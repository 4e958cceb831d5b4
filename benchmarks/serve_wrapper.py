"""Start the program's own CLI, unchanged, with two things added that only
the process holding the chip can do:

- the model of the run's seed: ``BENCH_WEIGHT_SEED`` becomes the engine's
  ``EngineConfig.seed`` (the CLI offers no option for it and always builds
  seed 0);
- the device trace: on SIGUSR1 a thread starts ``jax.profiler``, holds a
  ``TraceAnnotation`` named ``bench_window`` open for ``BENCH_TRACE_S``
  seconds, stops the profiler and writes ``done.json`` beside the trace.
  The annotation is on the trace's own clock, so the reduction knows the
  window's edges and counts idle time at them.

Everything else — arguments, engine, tunnel — is ``python -m
p2p_llm_tunnel_tpu.cli``.
"""

from __future__ import annotations

import json
import os
import runpy
import signal
import sys
import threading
import time


def _seed_engine(seed: int) -> None:
    from p2p_llm_tunnel_tpu.engine import engine as engine_mod

    config = engine_mod.EngineConfig
    if "seed" not in getattr(config, "__dataclass_fields__", {}):
        raise SystemExit("EngineConfig has no `seed` field any more: the "
                         "benchmark cannot give the run's seed its model")
    plain_init = config.__init__

    def seeded_init(self, *args, **kwargs):
        plain_init(self, *args, **kwargs)
        object.__setattr__(self, "seed", seed)

    config.__init__ = seeded_init


def _trace_on_signal(trace_dir: str, seconds: float) -> None:
    def record() -> None:
        import jax

        # device and host events only: the Python tracer would slow the
        # event loop it traces and fill the file with interpreter frames
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(trace_dir, profiler_options=options)
        t0 = time.monotonic()
        with jax.profiler.TraceAnnotation("bench_window"):
            time.sleep(seconds)
        t1 = time.monotonic()
        jax.profiler.stop_trace()
        with open(os.path.join(trace_dir, "done.json"), "w") as f:
            json.dump({"t0": t0, "t1": t1, "written": time.monotonic()}, f)

    def on_signal(_signum, _frame) -> None:
        # off the main thread: the event loop keeps serving while the
        # profiler starts, sleeps and writes
        threading.Thread(target=record, name="bench-trace",
                         daemon=True).start()

    signal.signal(signal.SIGUSR1, on_signal)


def main() -> None:
    _seed_engine(int(os.environ["BENCH_WEIGHT_SEED"]))
    trace_dir = os.environ.get("BENCH_TRACE_DIR")
    if trace_dir:
        _trace_on_signal(trace_dir, float(os.environ["BENCH_TRACE_S"]))
    sys.argv = ["p2p_llm_tunnel_tpu.cli"] + sys.argv[1:]
    runpy.run_module("p2p_llm_tunnel_tpu.cli", run_name="__main__")


if __name__ == "__main__":
    main()
