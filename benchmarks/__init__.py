"""The benchmark (see BENCHMARK.json and PERF.md): ``run.py`` is the command,
``harness.py`` drives one cell, everything else is found by name."""
