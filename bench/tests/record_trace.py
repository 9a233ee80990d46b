"""Record the small device trace the reduction's test reads.

    python bench/tests/record_trace.py <out.json>

Runs a few float64 matrix products on the chip under the benchmark's own
capture (harness/devtrace.py), with host pauses between them, and writes
the flattened events of the window (load_events) as JSON.
"""

import json
import sys
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(BENCH / "harness")]

import devtrace  # noqa: E402


def main(out: str) -> int:
    import jax
    import jax.numpy as jnp

    if jax.devices()[0].platform != "tpu":
        print("record_trace: needs a TPU", file=sys.stderr)
        return 2
    with jax.enable_x64(True):
        f = jax.jit(lambda x: jnp.tanh(x @ x).sum())
        x = jnp.ones((512, 512), dtype=jnp.float64)
        f(x).block_until_ready()
        out_dir = BENCH.parent / ".bench_out" / "record_trace"
        with devtrace.capture(out_dir):
            for _ in range(3):
                with jax.profiler.TraceAnnotation("host.pause"):
                    time.sleep(0.02)
                f(x).block_until_ready()
    events = devtrace.load_events(out_dir)
    keep = {"device": events["device"], "host": [
        h for h in events["host"] if h[1] in (devtrace.WINDOW, "host.pause")
    ]}
    with open(out, "w") as fh:
        json.dump(keep, fh)
    print(json.dumps(devtrace.reduce(keep, 1)))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
