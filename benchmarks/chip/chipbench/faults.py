"""Faults planted in the timed path, to show that ``correct`` fails them.

Each fault takes the serving engine before its warm-up and breaks its decode
steps the way a faulty change to the program could; the run then goes on
unchanged.  ``calibrate.py --fault <name>`` reads a fault at the cell's
own size on the chip, and the CPU tests read each at a cut-down size.

    state_unchanged  decode steps never write the new positions' keys and
                     values: the KV cache leaves each step as it came in
    half_batch       only the first half of the slots is computed; the
                     other half is handed the first half's tokens
    token_altered    every decode step alters the token it produces for
                     one slot, a different slot each step
"""
from __future__ import annotations

import itertools
from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np


def _wrap_outputs(engine, tokens_fn) -> None:
    """Apply ``tokens_fn`` to the tokens each decode step returns: [n] of
    the single step, [K, n] of a burst."""
    slots, burst = engine._decode_slots, engine._decode_burst

    def decode_slots(*args):
        toks, cache = slots(*args)
        return tokens_fn(toks), cache

    def decode_burst(*args):
        cache, toks, valid = burst(*args)
        return cache, tokens_fn(toks), valid
    engine._decode_slots, engine._decode_burst = decode_slots, decode_burst


def state_unchanged(engine) -> None:
    from repro.models import attention

    def unwritten(fn):
        def traced(*args):
            with mock.patch.object(attention, "cache_write_rows",
                                   lambda slab, vals, rows, offsets: slab):
                return fn(*args)
        return traced
    engine._decode_slots = jax.jit(unwritten(engine._decode_slots_fn),
                                   donate_argnums=(2,))
    engine._decode_burst = jax.jit(unwritten(engine._decode_burst_fn),
                                   donate_argnums=(1,))


def half_batch(engine) -> None:
    def fold(toks):
        toks = np.array(toks)
        h = toks.shape[-1] // 2
        toks[..., h:2 * h] = toks[..., :h]
        return jnp.asarray(toks)
    _wrap_outputs(engine, fold)


def token_altered(engine) -> None:
    vocab = engine.cfg.vocab
    turn = itertools.count()

    def alter(toks):
        toks = np.array(toks)
        row = next(turn) % toks.shape[-1]
        toks[..., row] = (toks[..., row] + 1) % vocab
        return jnp.asarray(toks)
    _wrap_outputs(engine, alter)


FAULTS = {f.__name__: f for f in (state_unchanged, half_batch, token_altered)}
