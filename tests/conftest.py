import os
import random
import sys
import types

# smoke tests and benches must see the single real CPU device.
# Exception: the multi-device CI job (sharded serving) opts in
# explicitly with REPRO_ALLOW_MULTIDEVICE=1 + a SMALL forced device
# count.
assert os.environ.get("REPRO_ALLOW_MULTIDEVICE") == "1" or \
    "xla_force_host_platform_device_count" not in \
    os.environ.get("XLA_FLAGS", ""), \
    "do not force a host device count globally " \
    "(REPRO_ALLOW_MULTIDEVICE=1 overrides for the multi-device CI job)"

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))


def pytest_configure(config):
    # heavyweights (chaos / conformance / gradcheck matrices) opt out of
    # the tier-1 fast gate with @pytest.mark.slow; `make test-fast`
    # deselects them, the full-matrix CI job still runs everything
    config.addinivalue_line(
        "markers", "slow: heavyweight matrix tests excluded from the "
        "tier-1 fast gate (run via `make test` / the full CI job)")


def _install_hypothesis_stub():
    """Deterministic mini-``hypothesis`` for containers without the real
    package: samples a fixed number of pseudo-random examples per test.

    Supports exactly the surface the suite uses: ``given(**kwargs)``,
    ``settings``, ``strategies.integers/lists/sampled_from``.
    """
    try:
        import hypothesis  # noqa: F401
        return
    except ModuleNotFoundError:
        pass

    class _Strategy:
        def __init__(self, draw):
            self.draw = draw

    def integers(lo, hi):
        return _Strategy(lambda rng: rng.randint(lo, hi))

    def lists(elem, min_size=0, max_size=10):
        return _Strategy(lambda rng: [
            elem.draw(rng)
            for _ in range(rng.randint(min_size, max_size))
        ])

    def sampled_from(seq):
        seq = list(seq)
        return _Strategy(lambda rng: seq[rng.randrange(len(seq))])

    def given(**strategies):
        def deco(fn):
            # no functools.wraps: __wrapped__ would expose the inner
            # signature and make pytest hunt for fixtures named after
            # the strategy kwargs
            def wrapper(*args, **kwargs):
                # @settings may sit above or below @given
                n = getattr(wrapper, "_stub_max_examples",
                            getattr(fn, "_stub_max_examples", 20))
                rng = random.Random(0)
                for _ in range(n):
                    drawn = {k: s.draw(rng) for k, s in strategies.items()}
                    fn(*args, **kwargs, **drawn)
            wrapper.__name__ = fn.__name__
            wrapper.__doc__ = fn.__doc__
            wrapper._stub_max_examples = getattr(
                fn, "_stub_max_examples", 20)
            return wrapper
        return deco

    def settings(max_examples=20, **_kwargs):
        def deco(fn):
            fn._stub_max_examples = max_examples
            return fn
        return deco

    mod = types.ModuleType("hypothesis")
    mod.given = given
    mod.settings = settings
    strat_mod = types.ModuleType("hypothesis.strategies")
    strat_mod.integers = integers
    strat_mod.lists = lists
    strat_mod.sampled_from = sampled_from
    mod.strategies = strat_mod
    sys.modules["hypothesis"] = mod
    sys.modules["hypothesis.strategies"] = strat_mod


_install_hypothesis_stub()
