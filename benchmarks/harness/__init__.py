"""The benchmark's own code: traffic, drivers, arithmetic, trace reduction.

Nothing here is imported by the program under test; the files under
``families/`` and the two drivers (``serve.py``, ``train.py``) are the only
ones that import it. What belongs to one configuration, one traffic mix or one
per-layer metric is a file under ``configs/``, ``traffic/`` or ``metrics/``,
found by the name in ``BENCHMARK.json``; a model family, an optimizer and a
driver are files too (``families/<family>.py``, ``optimizers/<name>.py``,
``<driver>.py``), found by the name the configuration, job or traffic file
gives.
"""
