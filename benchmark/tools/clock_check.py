"""Do the program's clock and the profiler's agree? Run by hand on the chip:

    python3 -m benchmark.tools.clock_check [out_dir]

A 0.2 s trace with `host_tracer_level = 1` around 20 rounds of a
`TraceAnnotation` that holds one small jitted op and the wait for it. The
xplane's events count nanoseconds from the session's start, which the
`Task Environment` plane gives as `profile_start_time` on the UNIX epoch
(`span_reduce.session_start_ns`). Printed, in nanoseconds:

- `host`: how far the annotation's start in the host plane lies after the
  `now_ns()` read before it and before the one after it. Both are positive
  where the two clocks agree to within the two reads' distance.
- `device`: how far the device's run of the op starts after the `now_ns()`
  read before the call, and ends before the read after
  `block_until_ready` returned. Both positive: the device's events lie
  inside the host's interval; the smaller one bounds the skew.

PERF.md records the readings.
"""

from __future__ import annotations

import json
import os
import statistics
import sys
import time


def main(argv) -> int:
    import jax
    import jax.numpy as jnp

    from benchmark import span_reduce, trace_reduce
    from deeplearning4j_tpu.utils.tracing import now_ns

    out_dir = argv[1] if len(argv) > 1 else os.path.join(
        "chiprun_out", "clock_check")
    os.makedirs(out_dir, exist_ok=True)
    op = jax.jit(lambda a: (a @ a).sum())
    x = jnp.ones((2048, 2048), jnp.bfloat16)
    jax.block_until_ready(op(x))

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 1
    jax.profiler.start_trace(out_dir, profiler_options=options)
    reads = []
    for i in range(20):
        t0 = now_ns()
        with jax.profiler.TraceAnnotation(f"clock_check_{i}"):
            t1 = now_ns()
            jax.block_until_ready(op(x))
            t2 = now_ns()
        reads.append((t0, t1, t2))
        time.sleep(0.01)
    jax.profiler.stop_trace()

    planes = span_reduce.read_xspace(trace_reduce.newest_xplane(out_dir),
                                     want=lambda name: True)
    base = span_reduce.session_start_ns(planes)
    if base is None:
        print("the trace names no profile_start_time", file=sys.stderr)
        return 1
    marks, runs = {}, []
    for plane in planes:
        device = trace_reduce.DEVICE_PLANE.match(plane["name"])
        for line in plane["lines"]:
            for name, start, end, _ in span_reduce.events_of(
                    plane, line["name"], base):
                if device and line["name"] == trace_reduce.MODULES_LINE:
                    runs.append((start, end))
                elif not device and name.startswith("clock_check_"):
                    marks[int(name.rsplit("_", 1)[1])] = start
    runs.sort()
    host = [(marks[i] - t0, t1 - marks[i])
            for i, (t0, t1, _) in enumerate(reads) if i in marks]
    # the i-th run on the device belongs to the i-th round, where the
    # trace holds exactly the rounds' runs
    device = [(s - t1, t2 - e) for (s, e), (_, t1, t2) in zip(runs, reads)] \
        if len(runs) == len(reads) else []

    def summary(pairs):
        if not pairs:
            return None
        after, before = zip(*pairs)
        return {"n": len(pairs),
                "after_first_read_ns": {"min": min(after),
                                        "median": statistics.median(after)},
                "before_second_read_ns": {"min": min(before),
                                          "median": statistics.median(before)}}

    print(json.dumps({"device": str(jax.devices()[0].device_kind),
                      "session_start_ns": base,
                      "annotations_found": len(marks),
                      "device_runs_found": len(runs),
                      "host": summary(host), "device_events": summary(device)}))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
