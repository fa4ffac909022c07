"""The weave kernels' 1-D gather and sorted-search primitives.

TPU has no hardware gather: XLA lowers ``table[idx]`` to per-element
HBM transactions, the most expensive primitive in the kernel ladder.
``searchsorted_iota_right`` therefore avoids a per-query binary search
altogether: one scatter-add histogram and a prefix sum.
"""

from __future__ import annotations

import jax.numpy as jnp

__all__ = ["take1d", "searchsorted_iota_right", "searchsorted_targets_left"]


def take1d(table, idx):
    """The kernels' gather from a full-width lane table."""
    from ..obs import span

    with span("weave.gather", width=int(table.shape[-1])):
        return table[idx]


def searchsorted_iota_right(keys_cum, q: int):
    """``searchsorted(keys_cum, arange(q), side="right")`` for a
    NON-DECREASING ``keys_cum``: histogram the keys (one scatter-add)
    and prefix-sum."""
    from ..obs import span

    with span("weave.search", site="iota_right", q=int(q)):
        hist = jnp.zeros(q + 1, jnp.int32).at[
            jnp.clip(keys_cum, 0, q)
        ].add(1, mode="drop")
        return jnp.cumsum(hist[:q]).astype(jnp.int32)


def searchsorted_targets_left(keys_cum, k: int):
    """``searchsorted(keys_cum, arange(1, k + 1), side="left")`` for a
    NON-DECREASING ``keys_cum`` — streaming form. ``left`` with target
    t counts keys strictly below t, i.e. keys <= t-1 — the identical
    histogram prefix as the iota/right case with targets shifted one,
    so this IS that function under another contract."""
    return searchsorted_iota_right(keys_cum, k)
