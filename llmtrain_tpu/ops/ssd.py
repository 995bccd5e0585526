"""State-space duality (Mamba-2) scan: the chunked matrix form for a slab
of tokens and the one-token state update, in plain ``jax.numpy``.

The recurrence, per head ``h`` (``A`` one negative scalar a head, ``dt``
positive, ``B`` / ``C`` shared by the heads of a group)::

    S_t = exp(dt_t * A) * S_{t-1} + dt_t * x_t (outer) B_t      S: (p, n)
    y_t = S_t C_t

:func:`ssd_chunked_scan` computes it for ``l`` positions in chunks: inside
a chunk the masked ``C B^T`` product weighted by the segment sums of
``dt * A`` (two matrix products), between chunks the carried state. It
takes an initial state and returns the final one, so a prompt may arrive
in slabs. Positions at or beyond ``true_len`` get ``dt = 0``: decay 1 and
nothing added, which leaves the state exactly where the last real token
left it (their ``y`` is finite and meaningless). :func:`ssd_step` is the
same recurrence for one token over a whole table of state rows.
:func:`ssm_conv` is the causal depthwise convolution in front of both,
with its own carried state (the last ``width - 1`` inputs).

Matrix products take their operands in ``dtype`` and accumulate in
float32; ``dt``, ``exp(dt * A)``, the recurrence and the state are float32
throughout. Differentiable as written (no custom rule), so the full
forward trains on any backend. No Pallas kernel: each function runs under
a ``jax.named_scope`` of its own name so a trace can say what one would
be worth.
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp


def ssm_conv(
    x: jax.Array,  # (b, l, c) this call's inputs
    weight: jax.Array,  # (width, c); weight[-1] multiplies the current input
    bias: jax.Array,  # (c,)
    state: jax.Array,  # (b, width - 1, c) the inputs before x[:, 0]
    true_len: jax.Array | None = None,  # (b,) real positions of x; None = all
) -> tuple[jax.Array, jax.Array]:
    """Causal depthwise conv1d; returns ``(out (b, l, c) float32, new state)``.
    The new state is the last ``width - 1`` REAL inputs (reaching back into
    ``state`` when fewer than that are real), in ``state``'s dtype."""
    with jax.named_scope("ssm_conv"):
        width, length = weight.shape[0], x.shape[1]
        full = jnp.concatenate([state.astype(x.dtype), x], axis=1)
        w = weight.astype(jnp.float32)
        out = bias.astype(jnp.float32)
        for k in range(width):
            out = out + full[:, k : k + length].astype(jnp.float32) * w[k]
        if true_len is None:
            new_state = full[:, length:]
        else:
            # full[true_len + j] is input number true_len - (width - 1) + j.
            idx = true_len[:, None] + jnp.arange(width - 1)[None, :]
            new_state = jnp.take_along_axis(full, idx[:, :, None], axis=1)
        return out, new_state.astype(state.dtype)


def ssd_chunked_scan(
    x: jax.Array,  # (b, l, h, p)
    dt: jax.Array,  # (b, l, h) float32, after softplus
    a: jax.Array,  # (h,) float32, negative
    b_mat: jax.Array,  # (b, l, g, n)
    c_mat: jax.Array,  # (b, l, g, n)
    *,
    chunk: int,
    initial_state: jax.Array | None = None,  # (b, h, p, n) float32
    true_len: jax.Array | None = None,  # (b,)
    dtype: Any = jnp.float32,
) -> tuple[jax.Array, jax.Array]:
    """``(y (b, l, h, p) float32, final state (b, h, p, n) float32)``."""
    with jax.named_scope("ssd_scan"):
        bsz, length, heads, p = x.shape
        groups, n = b_mat.shape[2], b_mat.shape[3]
        r = heads // groups
        dt = dt.astype(jnp.float32)
        if true_len is not None:
            real = jnp.arange(length)[None, :] < true_len[:, None]
            dt = jnp.where(real[:, :, None], dt, 0.0)
        pad = -length % chunk
        if pad:
            # dt = 0 on the padding: it moves neither the state nor a real y.
            x, dt, b_mat, c_mat = (
                jnp.pad(v, [(0, 0), (0, pad)] + [(0, 0)] * (v.ndim - 2))
                for v in (x, dt, b_mat, c_mat)
            )
        nc = (length + pad) // chunk
        xc = x.reshape(bsz, nc, chunk, groups, r, p)
        dtc = dt.reshape(bsz, nc, chunk, groups, r)
        bc = b_mat.reshape(bsz, nc, chunk, groups, n).astype(dtype)
        cc = c_mat.reshape(bsz, nc, chunk, groups, n).astype(dtype)
        # Inclusive sums of dt * A inside each chunk: exp(cs[i] - cs[j]) is
        # the decay from just after position j to position i.
        cs = jnp.cumsum(dtc * a.astype(jnp.float32).reshape(groups, r), axis=2)
        xdt = xc.astype(jnp.float32) * dtc[..., None]  # (b, c, L, g, r, p)

        # 1. Inside a chunk: (C B^T, masked and decayed) x.
        seg = cs[:, :, :, None] - cs[:, :, None, :]  # (b, c, Li, Lj, g, r)
        causal = jnp.tril(jnp.ones((chunk, chunk), bool))[None, None, :, :, None, None]
        decay = jnp.exp(jnp.where(causal, seg, -jnp.inf))
        cb = jnp.einsum(
            "bclgn,bcsgn->bclsg", cc, bc, preferred_element_type=jnp.float32
        )
        scores = (cb[..., None] * decay).astype(dtype)  # (b, c, Li, Lj, g, r)
        y = jnp.einsum(
            "bclsgr,bcsgrp->bclgrp", scores, xdt.astype(dtype),
            preferred_element_type=jnp.float32,
        )

        # 2. What each chunk adds to the state at its own end.
        to_end = jnp.exp(cs[:, :, -1:] - cs)  # (b, c, L, g, r)
        added = jnp.einsum(
            "bclgn,bclgrp->bcgrpn", bc, (xdt * to_end[..., None]).astype(dtype),
            preferred_element_type=jnp.float32,
        )

        # 3. Between chunks: the carried state, float32.
        if initial_state is None:
            carried = jnp.zeros((bsz, groups, r, p, n), jnp.float32)
        else:
            carried = initial_state.astype(jnp.float32).reshape(bsz, groups, r, p, n)
        chunk_decay = jnp.exp(cs[:, :, -1])  # (b, c, g, r)

        def step(state, inp):
            dec, add = inp
            return state * dec[..., None, None] + add, state

        final, entering = jax.lax.scan(
            step, carried, (jnp.moveaxis(chunk_decay, 1, 0), jnp.moveaxis(added, 1, 0))
        )
        entering = jnp.moveaxis(entering, 0, 1)  # (b, c, g, r, p, n)

        # 4. The entering state's share of every position's output.
        y_off = jnp.einsum(
            "bclgn,bcgrpn->bclgrp", cc, entering.astype(dtype),
            preferred_element_type=jnp.float32,
        )
        y = y + y_off * jnp.exp(cs)[..., None]
        y = y.reshape(bsz, nc * chunk, heads, p)[:, :length]
        return y, final.reshape(bsz, heads, p, n)


def ssd_step(
    state: jax.Array,  # (rows, h, p, n) float32
    x: jax.Array,  # (rows, h, p)
    dt: jax.Array,  # (rows, h) float32; 0 leaves a row's state as it is
    a: jax.Array,  # (h,)
    b_mat: jax.Array,  # (rows, g, n)
    c_mat: jax.Array,  # (rows, g, n)
    keep: jax.Array | None = None,  # (rows,) bool; False starts a row from zero
) -> tuple[jax.Array, jax.Array]:
    """One token for every row: ``(new state, y (rows, h, p) float32)``.
    Elementwise over the state, so a donated state is updated in place."""
    with jax.named_scope("ssd_step"):
        rows, heads, p, n = state.shape
        groups = b_mat.shape[1]
        r = heads // groups
        dt = dt.astype(jnp.float32)
        decay = jnp.exp(dt * a.astype(jnp.float32))
        if keep is not None:
            decay = jnp.where(keep[:, None], decay, 0.0)
        s = state.reshape(rows, groups, r, p, n)
        xdt = (x.astype(jnp.float32) * dt[..., None]).reshape(rows, groups, r, p)
        b32 = b_mat.astype(jnp.float32)[:, :, None, None, :]
        c32 = c_mat.astype(jnp.float32)[:, :, None, None, :]
        new = s * decay.reshape(rows, groups, r)[..., None, None] + xdt[..., None] * b32
        y = jnp.sum(new * c32, axis=-1)
        return new.reshape(state.shape), y.reshape(rows, heads, p)


__all__ = ["ssd_chunked_scan", "ssd_step", "ssm_conv"]
