"""The gated delta rule (Gated DeltaNet, Yang et al. 2024): a linear-attention
layer whose per-head state is a ``[key_dim, value_dim]`` matrix ``S``,

    S <- exp(g_t) S;  d = beta_t (v_t - S^T k_t);  S <- S + k_t d^T;  o_t = S^T q_t

`gated_delta_step` is that recurrence for one token a row (decode);
`gated_delta_prefill` covers a whole segment in chunks of ``chunk`` tokens
(the WY form of the FLA kernels: inside a chunk the rule is one triangular
system, inverted by `unit_lower_inverse`, and a few matmuls; between chunks a
`lax.scan` carries ``S``), and `causal_conv_prefill` / `causal_conv_step` are
the short depthwise convolution in front of it. All plain XLA under
`jax.named_scope`s (``delta_step`` / ``delta_prefill``) so the device trace can
find them.

Precision: ``S`` and the arithmetic on it stay float32 at `HIGHEST` — the TPU
otherwise multiplies float32 operands in one bf16 pass, which is a different
state after a few hundred tokens. The matmuls here are small beside the
projections around them.

Ragged rows: a row whose true length is shorter than the segment passes
``g = 0, beta = 0`` for its pad tokens (`mask_pad`): no decay, no write, so the
state after the segment is the state after the row's last real token.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


def mask_pad(g: jax.Array, beta: jax.Array, lengths: jax.Array | None):
    """Zero ``g`` and ``beta`` ([b, t, h]) at positions >= ``lengths`` ([b])."""
    if lengths is None:
        return g, beta
    real = (jnp.arange(g.shape[1])[None, :] < lengths[:, None])[..., None]
    return jnp.where(real, g, 0.0), jnp.where(real, beta, 0.0)


def gated_delta_step(
    state: jax.Array,  # [b, h, dk, dv] float32
    q: jax.Array,  # [b, h, dk]
    k: jax.Array,  # [b, h, dk]
    v: jax.Array,  # [b, h, dv]
    g: jax.Array,  # [b, h] log decay (<= 0)
    beta: jax.Array,  # [b, h]
) -> tuple[jax.Array, jax.Array]:
    """One token: returns ``(new_state, o [b, h, dv])`` in float32. Products
    with the state are elementwise-and-sum on purpose: a batched matvec gives
    the MXU nothing, and the VPU keeps float32."""
    with jax.named_scope("delta_step"):
        q, k, v = (x.astype(jnp.float32) for x in (q, k, v))
        state = state * jnp.exp(g.astype(jnp.float32))[..., None, None]
        read = jnp.sum(state * k[..., :, None], axis=-2)  # S^T k
        d = beta.astype(jnp.float32)[..., None] * (v - read)
        state = state + k[..., :, None] * d[..., None, :]
        return state, jnp.sum(state * q[..., :, None], axis=-2)


def unit_lower_inverse(system: jax.Array) -> jax.Array:
    """The inverse of unit lower triangular matrices ``[..., c, c]`` by
    halves, ``[[A, 0], [C, B]]^-1 = [[A^-1, 0], [-B^-1 C A^-1, B^-1]]``, the two
    diagonal blocks of a level inverted in one batched call: some
    ``2 log2(c)`` small matmuls where a triangular solve walks the ``c`` rows
    one after the other (on the TPU 2 ms a layer of a 1536-token prefill). A
    block of 8 or fewer is ``I - N`` with ``N^8 = 0``, and its inverse the
    finite series ``(I + N)(I + N^2)(I + N^4)``. The series alone over the
    whole chunk would not do: for a run of equal tokens (equal keys) its terms
    reach 1e10 where the inverse's entries are under 1, and float32 cancels
    them into garbage."""
    c = system.shape[-1]
    if c <= 8:
        eye = jnp.eye(c, dtype=system.dtype)
        power = eye - system  # N, strictly lower
        inverse = eye + power
        for _ in range(max(0, (c - 1).bit_length() - 1)):
            power = jnp.matmul(power, power, precision=HIGHEST)
            inverse = inverse + jnp.matmul(inverse, power, precision=HIGHEST)
        return inverse
    h = c // 2
    if c % 2 == 0:
        a, b = unit_lower_inverse(jnp.stack([system[..., :h, :h], system[..., h:, h:]]))
    else:
        a, b = unit_lower_inverse(system[..., :h, :h]), unit_lower_inverse(system[..., h:, h:])
    below = -jnp.matmul(jnp.matmul(b, system[..., h:, :h], precision=HIGHEST), a, precision=HIGHEST)
    top = jnp.concatenate([a, jnp.zeros(a.shape[:-1] + (c - h,), a.dtype)], axis=-1)
    return jnp.concatenate([top, jnp.concatenate([below, b], axis=-1)], axis=-2)


def gated_delta_prefill(
    q: jax.Array,  # [b, t, h, dk]
    k: jax.Array,  # [b, t, h, dk]
    v: jax.Array,  # [b, t, h, dv]
    g: jax.Array,  # [b, t, h] log decay
    beta: jax.Array,  # [b, t, h]
    state: jax.Array | None = None,  # [b, h, dk, dv] float32, zeros if None
    chunk: int = 64,
) -> tuple[jax.Array, jax.Array]:
    """A whole segment: returns ``(o [b, t, h, dv], final_state)`` in float32,
    equal to ``t`` calls of `gated_delta_step`."""
    b, t, h, dk = q.shape
    dv = v.shape[-1]
    pad = (-t) % chunk
    with jax.named_scope("delta_prefill"):
        def chunks(x):  # [b, t, h, ...] -> [n, b, h, chunk, ...]
            x = x.astype(jnp.float32)
            if pad:
                x = jnp.pad(x, ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2))
            x = x.reshape((b, (t + pad) // chunk, chunk) + x.shape[2:])
            return jnp.moveaxis(jnp.moveaxis(x, 3, 2), 1, 0)

        q, k, v, g, beta = chunks(q), chunks(k), chunks(v), chunks(g), chunks(beta)
        cum = jnp.cumsum(g, axis=-1)  # [n, b, h, c] log decay since the chunk began
        lower = jnp.tril(jnp.ones((chunk, chunk), bool))
        # decay from token j to token i >= j; the exponent is masked first so
        # that the upper triangle never overflows
        decay = jnp.exp(jnp.where(lower, cum[..., :, None] - cum[..., None, :], -jnp.inf))
        k_beta, v_beta = k * beta[..., None], v * beta[..., None]
        kk = jnp.einsum("...ik,...jk->...ij", k_beta, k, precision=HIGHEST) * decay
        # (I + strictly_lower(kk)) u = v_beta, w = k_beta * exp(cum): what each
        # token writes, given the writes of the tokens before it in the chunk
        system = jnp.where(jnp.tril(jnp.ones((chunk, chunk), bool), -1), kk, 0.0) \
            + jnp.eye(chunk, dtype=jnp.float32)
        rhs = jnp.concatenate([v_beta, k_beta * jnp.exp(cum)[..., None]], axis=-1)
        solved = jnp.einsum("...ij,...jv->...iv", unit_lower_inverse(system), rhs, precision=HIGHEST)
        u, w = solved[..., :dv], solved[..., dv:]
        qk = jnp.where(lower, jnp.einsum("...ik,...jk->...ij", q, k, precision=HIGHEST) * decay, 0.0)
        if state is None:
            state = jnp.zeros((b, h, dk, dv), jnp.float32)

        def one(S, xs):
            q_c, k_c, u_c, w_c, qk_c, cum_c = xs
            v_new = u_c - jnp.einsum("bhck,bhkv->bhcv", w_c, S, precision=HIGHEST)
            o = jnp.einsum("bhck,bhkv->bhcv", q_c * jnp.exp(cum_c)[..., None], S, precision=HIGHEST) \
                + jnp.einsum("bhij,bhjv->bhiv", qk_c, v_new, precision=HIGHEST)
            last = cum_c[..., -1:]
            S = S * jnp.exp(last)[..., None] + jnp.einsum(
                "bhck,bhcv->bhkv", k_c * jnp.exp(last - cum_c)[..., None], v_new, precision=HIGHEST)
            return S, o

        state, o = jax.lax.scan(one, state.astype(jnp.float32), (q, k, u, w, qk, cum))
        o = jnp.moveaxis(jnp.moveaxis(o, 0, 1), 3, 2).reshape(b, t + pad, h, dv)
        return o[:, :t], state


def causal_conv_prefill(
    x: jax.Array,  # [b, t, c]
    weight: jax.Array,  # [width, c] depthwise taps, oldest first
    lengths: jax.Array | None = None,  # [b] true lengths, or None for t
) -> tuple[jax.Array, jax.Array]:
    """Causal depthwise convolution over a segment that starts a sequence:
    returns ``(y [b, t, c], window [b, width - 1, c])``, the window being the
    last ``width - 1`` inputs before each row's true end (zeros where the row
    is shorter than that), which is what `causal_conv_step` needs next."""
    width, t = weight.shape[0], x.shape[1]
    padded = jnp.pad(x, ((0, 0), (width - 1, 0), (0, 0)))
    y = sum(padded[:, j: j + t] * weight[j].astype(x.dtype) for j in range(width))
    ends = jnp.full((x.shape[0],), t, jnp.int32) if lengths is None else lengths.astype(jnp.int32)
    # padded[ends + j] for j < width - 1 are inputs ends - (width - 1) + j
    window = jax.vmap(
        lambda row, n: jax.lax.dynamic_slice(row, (n, 0), (width - 1, row.shape[-1]))
    )(padded, ends)
    return y, window


def causal_conv_step(
    window: jax.Array,  # [b, width - 1, c] the last inputs
    x: jax.Array,  # [b, c] the new input
    weight: jax.Array,  # [width, c]
) -> tuple[jax.Array, jax.Array]:
    """One token: ``(y [b, c], new_window)``."""
    full = jnp.concatenate([window, x[:, None].astype(window.dtype)], axis=1)  # [b, width, c]
    y = jnp.sum(full * weight.astype(full.dtype)[None], axis=1)
    return y, full[:, 1:]
