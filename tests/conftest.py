import importlib.util
import os
import sys
import types

# Tests must see 1 CPU device (the dry-run sets its own flags in-process).
os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

# Property-based tests use hypothesis when installed; otherwise register the
# deterministic fallback (tests/_hypothesis_fallback.py) under the same name
# so those modules still collect and run.
try:
    import hypothesis  # noqa: F401
except ModuleNotFoundError:
    _spec = importlib.util.spec_from_file_location(
        "_hypothesis_fallback",
        os.path.join(os.path.dirname(__file__), "_hypothesis_fallback.py"),
    )
    _fb = importlib.util.module_from_spec(_spec)
    sys.modules["_hypothesis_fallback"] = _fb
    _spec.loader.exec_module(_fb)
    _hyp = types.ModuleType("hypothesis")
    _st = types.ModuleType("hypothesis.strategies")
    for _name in ("assume", "given", "settings", "Strategy"):
        setattr(_hyp, _name, getattr(_fb, _name))
    for _name in ("integers", "floats", "booleans", "lists", "sampled_from", "just"):
        setattr(_st, _name, getattr(_fb, _name))
    _hyp.strategies = _st
    sys.modules["hypothesis"] = _hyp
    sys.modules["hypothesis.strategies"] = _st

import jax

jax.config.update("jax_enable_x64", False)
