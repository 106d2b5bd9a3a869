"""Faults planted under the timed path: each takes the built engine and
breaks what its compiled programs produce. The comparison has to turn
``correct`` false under every one a cell can have."""
import jax
import jax.numpy as jnp


def token_altered(engine):
    """Every decode step emits the next token id instead of its own."""
    orig = engine._step_fn

    def step(*a, **k):
        tok, caches = orig(*a, **k)
        return (tok + 1) % engine.cfg.vocab_size, caches
    step._cache_size = orig._cache_size
    engine._step_fn = step


def state_unchanged(engine):
    """Every decode step returns the KV cache it was given."""
    orig = engine._step_fn

    def step(params, rp, tok, caches, *rest):
        keep = jax.tree.map(jnp.copy, caches)
        new_tok, _ = orig(params, rp, tok, caches, *rest)
        return new_tok, keep
    step._cache_size = orig._cache_size
    engine._step_fn = step


def head_topk_off(engine):
    """Requests below budget 1.0 keep one head fewer than the budget's
    solved row (an off-by-one in the routed head count)."""
    orig = engine._policy_for

    def policy_for(budget, depth=None):
        pol = orig(budget, depth)
        if pol is None or budget is None or budget >= 1.0:
            return pol
        return pol.replace(mha_head_topk=pol.mha_head_topk - 1)
    engine._policy_for = policy_for


FAULTS = {f.__name__: f for f in (token_altered, state_unchanged,
                                  head_topk_off)}
