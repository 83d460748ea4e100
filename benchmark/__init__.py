"""The benchmark of paddle_tpu: one command (`run.py`), driven by data.

`BENCHMARK.json` at the root of the repo names the cells; everything a
cell, a configuration, a traffic mix or a per-layer metric needs sits in
a file of its own under this directory (README.md says which)."""
