"""The serving sampling rule — ONE definition, shared by every path.

``sample_rows`` is the pure math: per-row greedy / temperature-scaled
categorical over [N, V] logits with per-row [N, 2] PRNG keys.  It is called
from three places that must agree bit-for-bit (DESIGN.md §11):

  * inside the fused ``decode_slots`` jit (engine.py) — sampling happens on
    device and only [n_slots] int32 token ids cross to the host;
  * inside every step of the ``decode_burst`` ``lax.scan`` (engine.py);
  * host-side for the first token sampled off a prompt's final prefill
    chunk (scheduler.py, via the jitted ``sample_tokens`` wrapper).

Greedy rows (temperature <= 0) never consume their key; temperature rows
use ``jax.random.categorical`` on ``logits / t``, which is a pure function
of (key, logits) — so a token sampled inside a K-step burst is bit-identical
to the same step run alone, as long as the same per-(request, step) key is
supplied (request.py's ``step_key`` / ``step_keys`` schedule).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.obs.span import span


@jax.named_scope("sample")
def sample_rows(logits, keys, temperatures):
    """Batched per-row sampling: logits [N, V], keys [N, 2], temps [N].
    Greedy when a row's temperature <= 0, else temperature-scaled
    categorical.  Pure — safe to call inside any jit."""
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    t = jnp.maximum(temperatures, jnp.float32(1e-6))[:, None]
    sampled = jax.vmap(jax.random.categorical)(keys, logits / t)
    return jnp.where(temperatures <= 0, greedy, sampled.astype(jnp.int32))


# host-side entry point (first-token sampling off prefill logits): one
# dispatch for a whole batch of rows
sample_tokens = jax.jit(sample_rows)


def sample_one(logits, key, temperature) -> int:
    """Single-row convenience over ``sample_tokens`` (N=1), so there is
    exactly one sampling rule in the system."""
    tok = sample_tokens(logits[None], jnp.asarray(key)[None],
                        jnp.asarray([temperature], jnp.float32))
    with span("serve.sync", what="first_token"):
        return int(np.asarray(tok)[0])


def batched_step_keys(seeds, ids, starts, k: int) -> np.ndarray:
    """[R, k, 2] uint32 key schedules for R requests in ONE computation and
    ONE blocking transfer: row r, step t is
    ``fold_in(fold_in(PRNGKey(seeds[r]), ids[r]), starts[r] + t)`` —
    bit-identical to ``Request.step_keys`` / ``step_key``, which define the
    contract (DESIGN.md §11).  The scheduler uses this for every decode
    round with temperature rows so key-schedule construction costs one
    host sync per round, not one per row."""
    seeds = jnp.asarray(seeds, jnp.int32)
    ids = jnp.asarray(ids, jnp.int32)
    starts = jnp.asarray(starts, jnp.int32)

    def one(seed, rid, n0):
        base = jax.random.fold_in(jax.random.PRNGKey(seed), rid)
        return jax.vmap(lambda s: jax.random.fold_in(base, s))(
            n0 + jnp.arange(k))

    keys = jax.vmap(one)(seeds, ids, starts)
    with span("serve.sync", what="keys"):
        return np.asarray(keys)
