"""Repo-level pytest bootstrap.

Three jobs:

1. Put ``src/`` on ``sys.path`` so ``PYTHONPATH=src`` is not strictly
   required (CI installs the package with ``pip install -e .`` anyway).

2. Provide a deterministic fallback for ``hypothesis`` when it is not
   installed.  The tier-1 suite uses a small slice of the hypothesis API
   (``given``/``settings``/a handful of strategies); in dependency-light
   containers that only ship jax+numpy+pytest the real package may be
   absent and the whole suite used to die at collection.  The fallback
   below runs each property test on ``max_examples`` seeded-random samples
   drawn from the same domains — strictly weaker than hypothesis (no
   shrinking, no edge-case database) but it keeps every property exercised.
   When the real ``hypothesis`` is importable (as in CI, via the dev
   extras) it is used untouched.

3. Give the benchmark's CPU fixture (``tests/bench/conftest.py``, whose
   ``tiny`` root cuts every configuration of ``BENCHMARK.json`` by its
   ``TINY`` table) the cuts of configurations and traffic mixes added
   after it, kept in ``tests/bench/tiny_cuts.py``.
"""
from __future__ import annotations

import os
import sys
import zlib

_SRC = os.path.join(os.path.dirname(__file__), "src")
if _SRC not in sys.path:
    sys.path.insert(0, _SRC)


def _install_hypothesis_fallback() -> None:
    import types

    import numpy as np

    class _Strategy:
        def __init__(self, draw):
            self._draw = draw

        def example(self, rng):
            return self._draw(rng)

    def integers(min_value, max_value):
        return _Strategy(lambda rng: int(rng.integers(min_value, max_value + 1)))

    def floats(min_value, max_value, width=64, **_):
        def draw(rng):
            x = float(rng.uniform(min_value, max_value))
            if width == 32:
                x = float(np.float32(x))
            return x

        return _Strategy(draw)

    def sampled_from(elements):
        elements = list(elements)
        return _Strategy(lambda rng: elements[int(rng.integers(len(elements)))])

    def booleans():
        return _Strategy(lambda rng: bool(rng.integers(2)))

    def just(value):
        return _Strategy(lambda rng: value)

    def lists(elements, min_size=0, max_size=10):
        def draw(rng):
            n = int(rng.integers(min_size, max_size + 1))
            return [elements.example(rng) for _ in range(n)]

        return _Strategy(draw)

    def text(alphabet="abcdefghij", min_size=0, max_size=10):
        chars = list(alphabet)
        def draw(rng):
            n = int(rng.integers(min_size, max_size + 1))
            return "".join(chars[int(rng.integers(len(chars)))] for _ in range(n))

        return _Strategy(draw)

    def tuples(*strategies):
        return _Strategy(lambda rng: tuple(s.example(rng) for s in strategies))

    def _as_strategy(x):
        return x if isinstance(x, _Strategy) else _Strategy(lambda rng: x)

    def arrays(dtype, shape, *, elements=None, **_):
        shape_s, elem_s = _as_strategy(shape), elements

        def draw(rng):
            shp = shape_s.example(rng)
            shp = (shp,) if isinstance(shp, int) else tuple(shp)
            if elem_s is None:
                return np.zeros(shp, dtype=dtype)
            flat = [elem_s.example(rng) for _ in range(int(np.prod(shp)))]
            return np.asarray(flat, dtype=dtype).reshape(shp)

        return _Strategy(draw)

    def settings(max_examples=10, deadline=None, **_):
        def deco(fn):
            fn._fallback_max_examples = max_examples
            return fn

        return deco

    def given(**kw_strategies):
        def deco(fn):
            # NB: no functools.wraps — pytest must NOT see the wrapped
            # function's parameters (it would treat them as fixtures).
            def wrapper():
                n = getattr(wrapper, "_fallback_max_examples", None) or getattr(
                    fn, "_fallback_max_examples", 10
                )
                seed = zlib.crc32(fn.__qualname__.encode())
                rng = np.random.default_rng(seed)
                for _ in range(n):
                    drawn = {k: s.example(rng) for k, s in kw_strategies.items()}
                    fn(**drawn)

            wrapper.__name__ = fn.__name__
            wrapper.__qualname__ = fn.__qualname__
            wrapper.__doc__ = fn.__doc__
            return wrapper

        return deco

    hyp = types.ModuleType("hypothesis")
    hyp.__doc__ = "Deterministic sampling fallback (real hypothesis not installed)."
    st_mod = types.ModuleType("hypothesis.strategies")
    for name, obj in [
        ("integers", integers), ("floats", floats), ("sampled_from", sampled_from),
        ("lists", lists), ("text", text), ("tuples", tuples),
        ("booleans", booleans), ("just", just),
    ]:
        setattr(st_mod, name, obj)
    extra_mod = types.ModuleType("hypothesis.extra")
    hnp_mod = types.ModuleType("hypothesis.extra.numpy")
    hnp_mod.arrays = arrays
    extra_mod.numpy = hnp_mod

    hyp.given = given
    hyp.settings = settings
    hyp.strategies = st_mod
    hyp.extra = extra_mod
    hyp.assume = lambda cond: None

    sys.modules["hypothesis"] = hyp
    sys.modules["hypothesis.strategies"] = st_mod
    sys.modules["hypothesis.extra"] = extra_mod
    sys.modules["hypothesis.extra.numpy"] = hnp_mod


try:
    import hypothesis  # noqa: F401
except ImportError:  # pragma: no cover - depends on the environment
    _install_hypothesis_fallback()
else:
    # Fixed-seed CI profile: derandomized (the same example sequence every
    # run, so property-suite failures bisect cleanly), no deadline (CPU
    # interpret-mode Pallas runs are slow), bounded example count.
    # Activated by HYPOTHESIS_PROFILE=ci in the CI workflow.
    hypothesis.settings.register_profile(
        "ci",
        max_examples=25,
        derandomize=True,
        deadline=None,
        database=None,
        print_blob=False,
    )
    _profile = os.environ.get("HYPOTHESIS_PROFILE")
    if _profile:
        hypothesis.settings.load_profile(_profile)


def pytest_plugin_registered(plugin, manager):
    """Merge ``tests/bench/tiny_cuts.py`` into the tables of the benchmark
    fixture's conftest as it registers."""
    path = getattr(plugin, "__file__", None) or ""
    if not path.endswith(os.path.join("tests", "bench", "conftest.py")):
        return
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "bench_tiny_cuts", os.path.join(os.path.dirname(path), "tiny_cuts.py")
    )
    cuts = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(cuts)
    for name in ("TINY", "TINY_TRAFFIC"):
        for key, value in getattr(cuts, name).items():
            getattr(plugin, name).setdefault(key, value)
