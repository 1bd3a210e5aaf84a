"""The chip benchmark of the NAVIS engine: one cell per run of ``run.py``.

A cell is a deployment (``configs/<name>.json``) under a traffic mix
(``mixes/<name>.json``); ``BENCHMARK.json`` at the repository root lists
the cells and the metrics, and each metric is read by a file of its own
(``end_to_end/<name>.py``, ``layer_metrics/<name>.py``).
"""
