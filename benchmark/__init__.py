"""The benchmark of paddle_tpu: the yardstick later PRs are held to.

Run one cell with ``python -m benchmark.run --workload <cell> --seed <n>
--seconds <s> --trace <0|1>`` from the repository's root; see README.md
in this directory.
"""
