"""The chip benchmark's harness.

Everything that belongs to one configuration, one traffic mix or one
per-layer metric lives in a file of its own beside this package
(`configs/`, `mixes/`, `metrics/`, `limits/`, `refs/`); the harness finds
each by the name `BENCHMARK.json` gives it.  From the program it takes only
the system under test (`repro.core.run_fed_chs` with the program's model,
engine, channels and kernels) and its spans and scope names.
"""
