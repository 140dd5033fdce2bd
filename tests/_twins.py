"""The repo's rule for two differently compiled programs of one
arithmetic (jit twins): XLA fuses their float reductions differently,
so sums may differ in the last place; counts, flags and selections
(min / max / top-k) may not."""
import jax
import numpy as np


def assert_ulp_equal(a, b, maxulp=1):
    """Exact on integer/bool leaves, <= ``maxulp`` on float leaves."""
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        x, y = np.asarray(x), np.asarray(y)
        if np.issubdtype(x.dtype, np.floating):
            np.testing.assert_array_max_ulp(x, y, maxulp=maxulp)
        else:
            assert np.array_equal(x, y)


def assert_attribution_twins(a, b, latency_sum, maxulp=1):
    """Two AttributionSummary twins.  ``residual`` = sum(client latency
    - attributed) and ``residual_abs`` cancel to ~1e-10 s: their last
    place is that of the sums they are the difference of, so they are
    held to a few float32 ULPs of the run's ``latency_sum``.  An
    exemplar's per-hop times are differences of absolute request
    clocks and are held to one ULP of the latest such clock."""
    cancel = 4 * np.spacing(np.float32(latency_sum))
    for f in ("residual", "residual_abs"):
        assert abs(float(getattr(a, f)) - float(getattr(b, f))
                   ) <= cancel, f
    same = dict(residual=0.0, residual_abs=0.0)
    if a.exemplars is not None:
        ea, eb = a.exemplars, b.exemplars
        clock = np.spacing(np.float32(np.max(ea.start + ea.latency)))
        for f in ("hop_latency", "hop_start"):
            np.testing.assert_allclose(
                np.asarray(getattr(ea, f)), np.asarray(getattr(eb, f)),
                rtol=0, atol=clock, err_msg=f,
            )
        same["exemplars"] = ea._replace(
            hop_latency=eb.hop_latency, hop_start=eb.hop_start
        )
    assert_ulp_equal(
        a._replace(**same),
        b._replace(residual=0.0, residual_abs=0.0), maxulp,
    )
