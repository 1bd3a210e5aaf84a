"""Device time of the ADC (``navis.adc``: ``kernels/ref.adc_distance``, the
one-hot select over each query's LUT) in the traced ``_search_many``, per
query: the sum over every scope path that ends in ``navis.adc`` (under
``navis.traverse`` and ``navis.entrance``).  An op has one path, so the
paths are disjoint and their sum is their union."""
from bench import scopes


def read(rec, trace):
    split = (scopes.of_run(rec, trace, __file__) or {}).get(
        "_search_many", {})
    s = sum(v for path, v in split.items()
            if path.rsplit("/", 1)[-1] == "navis.adc")
    n = sum(w["n"] for w in rec["search_waves"])
    return s * 1e3 / n if s and n else None
