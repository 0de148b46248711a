"""kvedge-tpu's benchmark: one cell of ``BENCHMARK.json`` per process.

``python3 -m benchmark.run --workload <cell> --seed <n> --seconds <s>
--trace <0|1>`` starts the serve payload through ``start_runtime``,
drives it over HTTP from a child process that never imports JAX, and
prints one JSON line. Everything that belongs to one configuration,
block, traffic mix, cell or per-layer metric is a file of its own under
``configs/``, ``references/``, ``traffic/``, ``cells/`` and ``metrics/``,
found by the name ``BENCHMARK.json`` or the configuration gives (a
configuration is ``configs/<config>.json``, one JSON object in the layout
at the top of ``cellspec.py``); PERF.md says why each exists.
"""
