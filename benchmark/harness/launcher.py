"""Traced server: the program's own `cmd_server`, with `jax.profiler`
switched on and off from outside.

`python benchmark/harness/launcher.py server -d ... -b ... --platform tpu`
runs exactly what `python -m pilosa_tpu.cli server ...` runs (the same
`main(argv)`); a daemon thread watches the control directory named by
BENCH_TRACE_CTL and starts the profiler when `start` appears there and
stops it when `stop` does. Only the process that holds the chip can
trace it, so the trace is taken here; the reduction to metrics is
`harness/trace_reduce.py`, run after this process has ended.
"""

import json
import os
import sys
import threading
import time


def _control(ctl: str) -> None:
    import jax

    def wait_for(name: str) -> None:
        path = os.path.join(ctl, name)
        while not os.path.exists(path):
            time.sleep(0.02)

    def mark(name: str, t: float) -> None:
        tmp = os.path.join(ctl, name + ".tmp")
        with open(tmp, "w") as f:
            json.dump({"time": t}, f)
        os.replace(tmp, os.path.join(ctl, name))

    wait_for("start")
    opts = jax.profiler.ProfileOptions()
    # Device planes and XLA's host events are what the reduction reads;
    # the Python tracer would weigh on 64 request threads and make the
    # file large.
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(os.path.join(ctl, "trace"),
                             profiler_options=opts)
    mark("started", time.time())
    wait_for("stop")
    t = time.time()
    jax.profiler.stop_trace()
    mark("stopped", t)


def main() -> int:
    ctl = os.environ["BENCH_TRACE_CTL"]
    checkout = os.getcwd()
    if checkout not in sys.path:
        sys.path.insert(0, checkout)
    from pilosa_tpu.cli.main import main as program_main

    threading.Thread(target=_control, args=(ctl,), daemon=True,
                     name="bench-trace-control").start()
    return program_main(sys.argv[1:])


if __name__ == "__main__":
    sys.exit(main())
