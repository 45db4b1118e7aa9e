"""The benchmark of tpu-dl: one command, data files, and the yardstick.

`python3 -m benchmark.run --workload <cell> --seed <n> --seconds <s>
--trace <0|1>` runs one cell of `BENCHMARK.json` once, on the chip.
See `benchmark/README.md` for how a later PR adds a configuration, a cell,
a traffic mix or a per-layer metric as files of its own.
"""
