"""Faults planted under the timed path, to show that ``correct`` sees them.

Each fault wraps the engine's ``round_fn`` (the compiled round the server
calls) and breaks what one round returns:

* ``unchanged``: the round returns the state it was given, so nothing is
  extracted, counted or retired;
* ``half_batch``: half of the round's chunks are left out of the slot
  statistics and the other half counted twice, keeping the total (the mean
  taken over the rest);
* ``answer_altered``: every slot's reported estimate is the next slot's.
* ``verdict_inverted``: every HAVING verdict a round reports is turned
  round.

The fourth kind, the exchange between chips left out, exists only on a
multi-chip mesh; no cell of this benchmark has one yet.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def _copy(tree):
    return jax.tree.map(lambda x: x + 0, tree)


_copy_jit = jax.jit(_copy)


def unchanged(fn):
    def round_(state, table, data, speeds):
        before = _copy_jit(state)
        _, rep = fn(state, table, data, speeds)
        return before, rep
    return round_


@jax.jit
def _halve(before, after):
    """Slot statistics of ``after`` with the round's deltas of even chunks
    dropped and of odd chunks doubled."""
    n = before.scan_m.shape[0]
    w = jnp.where(jnp.arange(n) % 2 == 0, 0.0, 2.0)

    def mix(a, b):
        return (a + (b - a) * w.astype(a.dtype)).astype(a.dtype)

    st = after.stats._replace(
        ysum=mix(before.stats.ysum, after.stats.ysum),
        ysq=mix(before.stats.ysq, after.stats.ysq),
        psum=mix(before.stats.psum, after.stats.psum))
    return after._replace(stats=st, gys=mix(before.gys, after.gys),
                          gyq=mix(before.gyq, after.gyq),
                          gps=mix(before.gps, after.gps))


def half_batch(fn):
    def round_(state, table, data, speeds):
        before = _copy_jit(state)
        after, rep = fn(state, table, data, speeds)
        return _halve(before, after), rep
    return round_


def answer_altered(fn):
    def round_(state, table, data, speeds):
        after, rep = fn(state, table, data, speeds)
        return after, rep._replace(estimate=jnp.roll(rep.estimate, 1),
                                   g_est=jnp.roll(rep.g_est, 1, axis=0))
    return round_


def verdict_inverted(fn):
    def round_(state, table, data, speeds):
        after, rep = fn(state, table, data, speeds)
        d = rep.decided
        return after, rep._replace(decided=jnp.where(d >= 0, 1 - d, d)
                                   .astype(d.dtype))
    return round_


FAULTS = {"unchanged": unchanged, "half_batch": half_batch,
          "answer_altered": answer_altered,
          "verdict_inverted": verdict_inverted}


def install(engine, name: str):
    """Wrap ``engine.round_fn`` with fault ``name``; returns the undo."""
    orig = engine.round_fn
    wrap = FAULTS[name]
    engine.round_fn = lambda b, mode="none": wrap(orig(b, mode))

    def undo():
        engine.round_fn = orig
    return undo
