"""System-invariant property test (hypothesis): on random fusion pyramids,
fused tile execution == monolithic execution."""

import jax
import numpy as np
from hypothesis import given, settings, strategies as st

from repro.core.executor import (
    fused_forward,
    init_pyramid_params,
    reference_forward,
)
from repro.core.fusion import FusedLevel, FusionSpec, lockstep_plan


@st.composite
def runnable_chain(draw):
    """Random conv/pool chain guaranteed to have positive output size."""
    size = draw(st.integers(12, 28))
    n_levels = draw(st.integers(1, 3))
    levels = []
    c = draw(st.integers(1, 3))
    cur = size
    for _ in range(n_levels):
        kind = draw(st.sampled_from(["conv", "conv", "pool"]))
        if kind == "conv":
            K = draw(st.integers(1, 4))
            S = draw(st.integers(1, 2))
            pad = draw(st.integers(0, max(0, K // 2)))
            nxt = (cur + 2 * pad - K) // S + 1
            if nxt < 2:
                continue
            c2 = draw(st.integers(1, 4))
            levels.append(FusedLevel("conv", K, S, pad, c, c2))
            c, cur = c2, nxt
        else:
            K = draw(st.integers(2, 3))
            nxt = (cur - K) // K + 1
            if nxt < 2:
                continue
            levels.append(FusedLevel("pool", K, K, 0, c, c))
            cur = nxt
    if not levels:
        levels = [FusedLevel("conv", 3, 1, 1, c, 2)]
    return FusionSpec(levels=tuple(levels), input_size=size)


class TestFusedExecutorProperty:
    @given(runnable_chain(), st.integers(1, 4), st.integers(0, 100))
    @settings(max_examples=25, deadline=None)
    def test_fused_equals_reference_on_random_pyramids(self, spec, region, seed):
        """THE system invariant: any fusion plan computes exactly what the
        monolithic network computes."""
        out_size = spec.feature_sizes()[-1]
        if out_size < 1:
            return
        region = min(region, out_size)
        params = init_pyramid_params(spec, jax.random.PRNGKey(seed))
        x = jax.random.normal(
            jax.random.PRNGKey(seed + 1),
            (1, spec.input_size, spec.input_size, spec.levels[0].n_in),
        )
        ref = reference_forward(x, spec, params)
        fused = fused_forward(x, spec, params, lockstep_plan(spec, region))
        np.testing.assert_allclose(
            np.asarray(fused), np.asarray(ref), atol=2e-4,
            err_msg=f"spec={spec} region={region}",
        )
