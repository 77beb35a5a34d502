"""The gated delta rule (Gated DeltaNet, Yang et al. 2024): a linear-attention
layer whose per-head state is a ``[key_dim, value_dim]`` matrix ``S``,

    S <- exp(g_t) S;  d = beta_t (v_t - S^T k_t);  S <- S + k_t d^T;  o_t = S^T q_t

`gated_delta_step` is that recurrence for one token a row (decode);
`gated_delta_prefill` covers a whole segment in chunks of ``chunk`` tokens
(the WY form of the FLA kernels: inside a chunk the rule is one triangular
system, inverted by `unit_lower_inverse`, and a few matmuls; between chunks a
`lax.scan` carries ``S``), and `causal_conv_prefill` / `causal_conv_step` are
the short depthwise convolution in front of it. On the TPU the decode step is
one Pallas kernel, `delta_step_kernel`, which reads ``S`` once and writes it
once in place (the XLA form reads it twice: the read against ``k`` has to
finish over the whole key axis before a row of the new ``S`` exists); the rest
is plain XLA. All of it runs under `jax.named_scope`s (``delta_step`` /
``delta_prefill``), which name the kernel's event (``%delta_step.N``).

The log decay ``g`` is one scalar a head (``[..., h]``: Gated DeltaNet) or one
a key channel (``[..., h, dk]``: Kimi Delta Attention, arXiv:2510.26692, ``S <-
Diag(exp(g_t)) S``; scopes ``kda_step`` / ``kda_prefill``). The step differs by
how ``exp(g)`` broadcasts over ``S``. The chunked form does not carry over: a
scalar decay multiplies ``k_i . k_j`` by one ``exp(cum_i - cum_j)``, a
per-channel one sits inside the contraction, ``sum_c k_i[c] exp(cum_i[c] -
cum_j[c]) k_j[c]``, and `kda_prefill` factors it around a reference that keeps
every exponent inside float32 (its docstring).

Precision: ``S`` and the arithmetic on it stay float32 at `HIGHEST` — the TPU
otherwise multiplies float32 operands in one bf16 pass, which is a different
state after a few hundred tokens. The matmuls here are small beside the
projections around them; the step's products are on the VPU, float32.

Ragged rows: a row whose true length is shorter than the segment passes
``g = 0, beta = 0`` for its pad tokens (`mask_pad`): no decay, no write, so the
state after the segment is the state after the row's last real token.
"""

from __future__ import annotations

import collections
import functools

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


def mask_pad(g: jax.Array, beta: jax.Array, lengths: jax.Array | None):
    """Zero ``g`` ([b, t, h] or [b, t, h, dk]) and ``beta`` ([b, t, h]) at
    positions >= ``lengths`` ([b])."""
    if lengths is None:
        return g, beta
    real = (jnp.arange(g.shape[1])[None, :] < lengths[:, None])[..., None]
    return jnp.where(real.reshape(real.shape + (1,) * (g.ndim - 3)), g, 0.0), jnp.where(real, beta, 0.0)


def gated_delta_step(
    state: jax.Array,  # [b, h, dk, dv] float32
    q: jax.Array,  # [b, h, dk]
    k: jax.Array,  # [b, h, dk]
    v: jax.Array,  # [b, h, dv]
    g: jax.Array,  # [b, h] log decay (<= 0), or [b, h, dk]: one a key channel
    beta: jax.Array,  # [b, h]
) -> tuple[jax.Array, jax.Array]:
    """One token: returns ``(new_state, o [b, h, dv])`` in float32. A head
    whose ``g`` is all zero and whose ``beta`` is zero keeps its state
    bit-equal, whatever it holds (the models pass that for a finished slot).
    On the TPU it is `delta_step_kernel`, one pass over ``S``; any other
    backend keeps this XLA body, products with the state elementwise-and-sum
    so that the VPU keeps float32."""
    from ..utils.environment import on_tpu_platform

    per_channel = g.ndim == k.ndim
    with jax.named_scope("kda_step" if per_channel else "delta_step"):
        if on_tpu_platform():
            DELTA_STEP_TRACES["pallas", state.shape[0]] += 1
            return delta_step_kernel(state, q, k, v, g, beta)
        DELTA_STEP_TRACES["xla", state.shape[0]] += 1
        q, k, v = (x.astype(jnp.float32) for x in (q, k, v))
        g, beta = g.astype(jnp.float32), beta.astype(jnp.float32)
        decay = jnp.exp(g)
        new = state * (decay[..., :, None] if per_channel else decay[..., None, None])
        read = jnp.sum(new * k[..., :, None], axis=-2)  # S^T k
        d = beta[..., None] * (v - read)
        new = new + k[..., :, None] * d[..., None, :]
        o = jnp.sum(new * q[..., :, None], axis=-2)
        still = (beta == 0) & (jnp.all(g == 0, axis=-1) if per_channel else g == 0)
        return jnp.where(still[..., None, None], state, new), o


# Decode steps of the delta rule by the path they took and their slot count,
# counted where the path is decided: when a program is TRACED. The kernel's
# tests read it; on the chip the device trace names the kernel that ran
# (`%delta_step.N` / `%kda_step.N` events in the benchmark's `device_ops`).
DELTA_STEP_TRACES: collections.Counter = collections.Counter()
DELTA_STEP_VMEM = 12 * 2**20  # bytes of state blocks in VMEM: one in and one out, two buffers each


def delta_head_block(h: int, dk: int, dv: int) -> int:
    """Heads a grid cell of `delta_step_kernel`, read off the state's shape:
    the most that divide ``h`` (and are ``h`` or a multiple of 8, the
    sublane tile of the ``[heads, dk]`` blocks) whose state blocks, in and
    out and two buffers each, fit `DELTA_STEP_VMEM`. Both delta-rule cells'
    ``[*, 32, 128, 128]`` take all 32 heads: a slot a cell, 2 MiB."""
    blocks = [hb for hb in range(h, 0, -1) if h % hb == 0 and (hb == h or hb % 8 == 0)]
    return next((hb for hb in blocks if 4 * hb * dk * dv * 4 <= DELTA_STEP_VMEM), blocks[-1])


def _delta_step_kernel(s_ref, q_ref, k_ref, v_ref, g_ref, beta_ref, s_out, o_out, *, per_channel):
    """One grid cell: ``hb`` heads of one slot. The block of ``S`` is read
    once and written once; a head at a time (``[dk, dv]``, 16 vregs) it is
    decayed, read against ``k``, written with ``k d^T`` and read against
    ``q``, all float32 on the VPU. ``q``, ``k`` (and a per-channel ``g``)
    arrive as rows ``[hb, dk]`` and are stood up once, ``[dk, hb]``, so that a
    head's vector lies along the key axis of its block of ``S``."""
    hb, _, dv = s_ref.shape
    rows = [q_ref[...], k_ref[...]] + ([g_ref[...]] if per_channel else [])
    cols = jnp.concatenate(rows, axis=0).T  # [dk, 2 hb] or [dk, 3 hb]
    beta = beta_ref[...]  # [1, hb]
    for j in range(hb):
        s = s_ref[j]
        q, k = cols[:, j: j + 1], cols[:, hb + j: hb + j + 1]
        b = beta[:, j: j + 1]
        if per_channel:
            g = cols[:, 2 * hb + j: 2 * hb + j + 1]  # [dk, 1]
            still = jnp.max(jnp.abs(g), axis=0, keepdims=True) == 0
        else:
            g = jnp.broadcast_to(g_ref[:, j: j + 1], (1, dv))  # Mosaic broadcasts one axis at a time
            still = g == 0
        new = s * jnp.exp(g)
        d = b * (v_ref[j: j + 1, :] - jnp.sum(new * k, axis=0, keepdims=True))  # [1, dv]
        new = new + k * d
        o_out[j: j + 1, :] = jnp.sum(new * q, axis=0, keepdims=True)
        # a finished head's block goes back as it came; Mosaic broadcasts a
        # [1, 1] mask over both axes only by way of a float row
        keep = jnp.broadcast_to(jnp.where(still & (b == 0), 1.0, 0.0), (1, dv))
        s_out[j] = jnp.where(keep != 0, s, new)


def delta_step_kernel(
    state: jax.Array,  # [b, h, dk, dv] float32, updated in place
    q: jax.Array,  # [b, h, dk]
    k: jax.Array,  # [b, h, dk]
    v: jax.Array,  # [b, h, dv]
    g: jax.Array,  # [b, h] or [b, h, dk]
    beta: jax.Array,  # [b, h]
    *,
    interpret: bool | None = None,
) -> tuple[jax.Array, jax.Array]:
    """`gated_delta_step` as one Pallas TPU kernel: grid (slots, head blocks
    of `delta_head_block`), ``S`` read once and written once into its own
    buffer (``input_output_aliases``), in its stored shape, so that XLA adds
    no copy of the donated cache leaf. Where a head's ``g`` is zero and its
    ``beta`` zero the block is written back as it was read. On the v5e the
    pass takes what a Pallas copy of ``S`` with the same blocks takes (77% of
    one read and one write at 819 GB/s; PERF.md section 6, PR 36): the
    arithmetic hides under the copies."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    from ..utils.environment import on_tpu_platform

    b, h, dk, dv = state.shape
    per_channel = g.ndim == k.ndim
    hb = delta_head_block(h, dk, dv)
    n = h // hb
    q, k, v, g, beta = (x.astype(jnp.float32) for x in (q, k, v, g, beta))
    if not per_channel:
        g = g.reshape(b, n, 1, hb)
    beta = beta.reshape(b, n, 1, hb)
    state_spec = pl.BlockSpec((None, hb, dk, dv), lambda i, j: (i, j, 0, 0))
    heads = lambda d: pl.BlockSpec((None, hb, d), lambda i, j: (i, j, 0))  # noqa: E731
    row = pl.BlockSpec((None, None, 1, hb), lambda i, j: (i, j, 0, 0))
    return pl.pallas_call(
        functools.partial(_delta_step_kernel, per_channel=per_channel),
        grid=(b, n),
        in_specs=[state_spec, heads(dk), heads(dk), heads(dv), heads(dk) if per_channel else row, row],
        out_specs=[state_spec, heads(dv)],
        out_shape=[jax.ShapeDtypeStruct(state.shape, jnp.float32),
                   jax.ShapeDtypeStruct((b, h, dv), jnp.float32)],
        input_output_aliases={0: 0},
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel", "parallel")),
        interpret=not on_tpu_platform() if interpret is None else interpret,
    )(state, q, k, v, g, beta)


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


def _chunked(x: jax.Array, chunk: int) -> jax.Array:
    """``[b, t, h, ...] -> [n, b, h, chunk, ...]`` in float32, ``t`` padded
    with zeros to whole chunks."""
    b, t = x.shape[:2]
    pad = (-t) % chunk
    x = x.astype(jnp.float32)
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2))
    x = x.reshape((b, (t + pad) // chunk, chunk) + x.shape[2:])
    return jnp.moveaxis(jnp.moveaxis(x, 3, 2), 1, 0)


def _unchunked(o: jax.Array, t: int) -> jax.Array:
    """``[n, b, h, chunk, dv] -> [b, t, h, dv]``, the pad dropped."""
    n, b, h, chunk, dv = o.shape
    return jnp.moveaxis(jnp.moveaxis(o, 0, 1), 3, 2).reshape(b, n * chunk, h, dv)[:, :t]


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
    equal to ``t`` calls of `gated_delta_step`. A per-channel ``g [b, t, h,
    dk]`` goes to `kda_prefill`."""
    if g.ndim == q.ndim:
        return kda_prefill(q, k, v, g, beta, state, chunk)
    b, t, h, dk = q.shape
    dv = v.shape[-1]
    with jax.named_scope("delta_prefill"):
        q, k, v, g, beta = (_chunked(x, chunk) for x in (q, k, v, g, beta))
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
        return _unchunked(o, t), state


KDA_SUB_CHUNK = 16  # tokens: with g >= -5 a token, |cum| inside one stays <= 80 < ln(float32 max) = 88.7


def kda_prefill(
    q: jax.Array,  # [b, t, h, dk]
    k: jax.Array,  # [b, t, h, dk]
    v: jax.Array,  # [b, t, h, dv]
    g: jax.Array,  # [b, t, h, dk] log decay a key channel, in [-5, 0]
    beta: jax.Array,  # [b, t, h]
    state: jax.Array | None = None,  # [b, h, dk, dv] float32, zeros if None
    chunk: int = 64,
) -> tuple[jax.Array, jax.Array]:
    """`gated_delta_prefill` for a decay that is a vector over the key
    channels: ``(o [b, t, h, dv], final_state)`` in float32, equal to ``t``
    calls of `gated_delta_step` with ``g [b, h, dk]``.

    The same WY form, with ``cum`` ``[.., c, dk]``. Between chunks nothing
    changes but the broadcast: every factor there is ``exp`` of something
    ``<= 0``. Inside a chunk the pair products ``P[i, j] = sum_c x_i[c]
    exp(cum_i[c] - cum_j[c]) k_j[c]`` (``x`` = ``k beta`` for the triangular
    system, ``q`` for the outputs; only ``j <= i`` is used) have to be one
    matmul, so the exponent is split around a reference ``r``: ``(x_i
    exp(cum_i - r)) . (k_j exp(r - cum_j))``. The plain choice ``r = 0``
    overflows float32 once ``-cum_j`` passes 88. Here the rows are cut into
    sub-chunks of `KDA_SUB_CHUNK` tokens and sub-chunk ``a`` takes ``r_a`` =
    ``cum`` at its start: the left factor is then in ``[exp(-80), 1]``; the
    right factor is ``<= 1`` for every ``j`` of an earlier sub-chunk and ``<=
    exp(80)`` inside the same one, given ``g >= -5`` a token (the bound KDA's
    gate keeps, ``kda_lower_bound``); later ``j`` are never used and are
    zeroed before the ``exp``. The materialised ``[c, c, dk]`` form would be
    1.6 GB for an admit of 4 x 1,536 tokens even at ``c = 16``."""
    b, t, h, dk = q.shape
    dv = v.shape[-1]
    sub = min(KDA_SUB_CHUNK, chunk)
    if chunk % sub:
        raise ValueError(f"chunk {chunk} is not a multiple of the sub-chunk {sub}")
    n_sub = chunk // sub
    with jax.named_scope("kda_prefill"):
        q, k, v, g, beta = (_chunked(x, chunk) for x in (q, k, v, g, beta))
        cum = jnp.cumsum(g, axis=-2)  # [n, b, h, c, dk] log decay since the chunk began
        lead = cum.shape[:-2]
        # r_a: cum at the last token before sub-chunk a (0 for the first)
        ref = jnp.concatenate([jnp.zeros(lead + (1, dk), jnp.float32),
                               cum[..., sub - 1:: sub, :][..., : n_sub - 1, :]], axis=-2)
        left = jnp.exp(cum - jnp.repeat(ref, sub, axis=-2))  # [.., c, dk], in [exp(-80), 1]
        seen = jnp.arange(chunk)[None, :] < (jnp.arange(n_sub)[:, None] + 1) * sub  # [n_sub, c]
        right = jnp.exp(jnp.where(seen[..., None], ref[..., :, None, :] - cum[..., None, :, :], -jnp.inf))
        k_right = k[..., None, :, :] * right  # [.., n_sub, c, dk]

        def pairs(x):  # [.., c, dk] -> P [.., c, c], right where j <= i
            x = (x * left).reshape(lead + (n_sub, sub, dk))
            return jnp.einsum("...aik,...ajk->...aij", x, k_right, precision=HIGHEST).reshape(
                lead + (chunk, chunk))

        k_beta, v_beta = k * beta[..., None], v * beta[..., None]
        system = jnp.where(jnp.tril(jnp.ones((chunk, chunk), bool), -1), pairs(k_beta), 0.0) \
            + jnp.eye(chunk, dtype=jnp.float32)
        rhs = jnp.concatenate([v_beta, k_beta * jnp.exp(cum)], axis=-1)
        solved = jnp.einsum("...ij,...jv->...iv", unit_lower_inverse(system), rhs, precision=HIGHEST)
        u, w = solved[..., :dv], solved[..., dv:]
        qk = jnp.where(jnp.tril(jnp.ones((chunk, chunk), bool)), pairs(q), 0.0)
        if state is None:
            state = jnp.zeros((b, h, dk, dv), jnp.float32)

        def one(S, xs):
            q_c, k_c, u_c, w_c, qk_c, cum_c = xs
            v_new = u_c - jnp.einsum("bhck,bhkv->bhcv", w_c, S, precision=HIGHEST)
            o = jnp.einsum("bhck,bhkv->bhcv", q_c * jnp.exp(cum_c), S, precision=HIGHEST) \
                + jnp.einsum("bhij,bhjv->bhiv", qk_c, v_new, precision=HIGHEST)
            last = cum_c[..., -1:, :]
            S = S * jnp.exp(last[..., 0, :, None]) + jnp.einsum(
                "bhck,bhcv->bhkv", k_c * jnp.exp(last - cum_c), v_new, precision=HIGHEST)
            return S, o

        state, o = jax.lax.scan(one, state.astype(jnp.float32), (q, k, u, w, qk, cum))
        return _unchunked(o, t), state


def _conv_window(padded: jax.Array, lengths: jax.Array | None, width: int, t: int) -> jax.Array:
    """The last ``width - 1`` inputs before each row's true end, from the
    inputs padded in front by ``width - 1`` zeros."""
    ends = jnp.full((padded.shape[0],), t, jnp.int32) if lengths is None else lengths.astype(jnp.int32)
    # padded[ends + j] for j < width - 1 are inputs ends - (width - 1) + j
    return jax.vmap(
        lambda row, n: jax.lax.dynamic_slice(row, (n, 0), (width - 1, row.shape[-1]))
    )(padded, ends)


def causal_conv_prefill(
    x: jax.Array,  # [b, t, c]
    weight: jax.Array,  # [width, c] depthwise taps, oldest first
    lengths: jax.Array | None = None,  # [b] true lengths, or None for t
    window_first: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """Causal depthwise convolution over a segment that starts a sequence:
    returns ``(y [b, t, c], window [b, width - 1, c])``, the window being the
    last ``width - 1`` inputs before each row's true end (zeros where the row
    is shorter than that), which is what `causal_conv_step` needs next.

    With ``window_first`` the window is taken first and leaves through an
    optimization barrier beside ``x``, from which ``y`` is then computed:
    where nothing else orders the program, XLA may put the window's slice at
    its end and keep ``x`` alive till then (Ling 3.0 flash's 6,144-token
    admit: five KDA layers' ``[4, 1536, 12288]``, 0.75 GB of workspace). It
    is asked for, not always taken: Qwen3-Next's 4 x 1,536-token admit fits
    without it, and took 104.0 ms with it against 101.6 without (TPU v5e)."""
    width, t = weight.shape[0], x.shape[1]
    padded = jnp.pad(x, ((0, 0), (width - 1, 0), (0, 0)))
    if window_first:
        window, x = jax.lax.optimization_barrier((_conv_window(padded, lengths, width, t), x))
        padded = jnp.pad(x, ((0, 0), (width - 1, 0), (0, 0)))
    y = sum(padded[:, j: j + t] * weight[j].astype(x.dtype) for j in range(width))
    if not window_first:
        window = _conv_window(padded, lengths, width, t)
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
