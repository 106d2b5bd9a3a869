import dataclasses

import jax
import pytest

# Tests run on the single real CPU device; multi-device tests start their
# own processes with fake CPU devices. Keep x64 off (match TPU numerics).
jax.config.update("jax_enable_x64", False)


@pytest.fixture
def key():
    return jax.random.PRNGKey(0)


def f32(cfg):
    """Smoke configs in float32 for tight numeric comparisons on CPU."""
    new = dataclasses.replace(cfg, dtype="float32")
    if cfg.encoder is not None:
        new = dataclasses.replace(
            new, encoder=dataclasses.replace(cfg.encoder, dtype="float32"))
    return new


# Two int8 serving paths quantize different f32 values (a ring prefill
# attends f32 K/V, a paged or replayed prefill its int8 pool; batch size
# and sharding reorder sums), so their logits differ by up to twice the
# 0.25 int8-vs-fp32 bound of test_backend's quantized grid.
INT8_TIE_TOL = 0.5


def assert_tokens_match_until_near_tie(engine, req, got, want,
                                       tol=INT8_TIE_TOL):
    """Greedy tokens of two serving paths: equal until the first position
    where they part; there the two candidates must be within ``tol`` of
    each other in ``engine``'s teacher-forced logits over the shared
    prefix — a near-tie that rounding alone can flip. (With random weights
    the top-2 margin is often that small.) Past it the continuations
    legitimately differ. Returns the position where they part, or None."""
    import jax.numpy as jnp
    import numpy as np
    from repro.models import forward
    got, want = np.asarray(got), np.asarray(want)
    assert len(got) == len(want), (got, want)
    n = next((i for i in range(len(want)) if got[i] != want[i]), None)
    if n is None:
        return None
    toks = np.concatenate([np.asarray(req.prompt, np.int32), want[:n]])
    logits, _ = forward(engine.params, engine.rp,
                        {"tokens": jnp.asarray(toks[None])}, engine.cfg,
                        engine.spec, mode=engine.mode,
                        policy=engine._policy_for(req.budget))
    last = np.asarray(logits[0, -1])
    gap = float(last[want[n]] - last[got[n]])
    assert gap <= tol, f"tokens part at {n} with logit gap {gap:.4f} > {tol}"
    return n
