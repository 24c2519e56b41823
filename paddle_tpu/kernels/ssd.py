"""Mamba-2 selective-scan kernels (Pallas TPU): the recurrence of a
state-space mixer with a scalar decay a head, over a whole prompt and over
one decode step.

Per head, with a state ``S`` in ``R^{P x N}`` (head dim by state dim), a
token brings an input ``u = dt x`` (``[P]``), a decay ``a = exp(-exp(A_log)
dt)`` in (0, 1], and ``B`` and ``C`` (``[N]``, one pair for all heads):

    S <- a S + u B^T;   y = S C

A row with ``a = 1`` and ``u = 0`` (``dt = 0``) leaves the state exactly as
it was: that is how padding and idle slots stand still. The convolution in
front, ``softplus``, the skip ``D x`` and the gate behind are the op's
(``ops/ssd.py``).

* :func:`ssd_chunk_scan` — a prompt, ``chunk`` (256) rows at a time, from a
  start state that is an input. Inside a chunk the rule is Mamba-2's dual
  form: with ``G_i`` the log-decay summed up to row ``i``,

      Y  = exp(G) (C S0^T) + (exp(G_i - G_j) (C_i . B_j))_{j <= i} U
      S' = exp(G_last) S0 + (exp(G_last - G) U)^T B

  so every product is a matrix product on the MXU and only the state
  crosses chunks, in f32 VMEM scratch. A grid step carries as many heads as
  fill 128 lanes (two of 64), so that ``U``, ``Y`` and the state keep the
  layout the projections give them. All of it f32 with true f32 products.
  Grid ``(sequence, group of heads, chunk)``, chunk innermost.
* :func:`ssd_decode_step` — one token for every slot: one pass over the
  state (read, decay, add, write, read out), bound by the state's bytes.
  The state is updated in place (``input_output_aliases``). Grid ``(slot,
  group of heads)``.

:func:`ssd_scan_reference` and :func:`ssd_step_reference` are the same rule
in plain ``jax.numpy`` (a ``lax.scan`` over tokens): the route the CPU
takes and the oracle of the parity tests. ``interpret=True`` runs the
kernels on the CPU.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .flash_attention import _PALLAS_SCOPE, _out_sds
from .gdn import _NT, _TN, _dot

__all__ = ["ssd_chunk_scan", "ssd_decode_step", "ssd_scan_reference",
           "ssd_step_reference", "SSD_CHUNK", "heads_per_step"]

SSD_CHUNK = 256
F32 = jnp.float32
_HI = jax.lax.Precision.HIGHEST
_LANES = 128


# --------------------------------------------------------------------------
# the rule in plain jax.numpy
# --------------------------------------------------------------------------

def ssd_step_reference(state, u, decay, b, c):
    """One token a sequence: ``state`` [B, H, P, N], ``u`` (= dt x)
    [B, H, P], ``decay`` [B, H], ``b``/``c`` [B, N]. Returns ``(y [B, H,
    P], state')``."""
    s = (state * decay[..., None, None]
         + u[..., None] * b[:, None, None, :])
    return jnp.einsum("bhpn,bn->bhp", s, c, precision=_HI), s


def ssd_scan_reference(u, g, b, c, state):
    """A token at a time from ``state`` [R, H, P, N]: ``u`` [R, S, H, P],
    ``g`` (log-decay, <= 0) [R, S, H], ``b``/``c`` [R, S, N]. Returns ``(y
    [R, S, H, P], final state)``."""
    def one(s, t):
        ut, gt, bt, ct = t
        y, s = ssd_step_reference(s, ut, jnp.exp(gt), bt, ct)
        return s, y

    rows = lambda t: jnp.moveaxis(t, 1, 0)
    s, y = jax.lax.scan(one, state.astype(F32),
                        (rows(u), rows(g), rows(b), rows(c)))
    return jnp.moveaxis(y, 0, 1), s


# --------------------------------------------------------------------------
# the chunked scan
# --------------------------------------------------------------------------

def heads_per_step(H: int, P: int) -> int:
    """Heads a grid step of the scan carries side by side in the lanes."""
    hp = max(1, _LANES // P)
    return hp if H % hp == 0 else 1


def _scan_kernel(hp, P, u_ref, b_ref, c_ref, g_ref, s0_ref, y_ref, sf_ref,
                 s_scr):
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        s_scr[:] = s0_ref[0, 0]

    u, b, c = u_ref[0], b_ref[0], c_ref[0]       # [L, hp P], [L, N], [L, N]
    L, W = u.shape
    ii = jax.lax.broadcasted_iota(jnp.int32, (L, L), 0)
    jj = jax.lax.broadcasted_iota(jnp.int32, (L, L), 1)
    eye, seen = ii == jj, ii >= jj
    cb = _dot(c, b, _NT)                                    # C_i . B_j
    lane = jax.lax.broadcasted_iota(jnp.int32, (L, W), 1)
    srow = jax.lax.broadcasted_iota(jnp.int32, (W, 1), 0)
    y = jnp.zeros((L, W), F32)
    eg = jnp.zeros((L, W), F32)          # exp(G_i), by the lane's head
    left = jnp.zeros((L, W), F32)        # exp(G_last - G_i)
    keep = jnp.zeros((W, 1), F32)        # exp(G_last), by the row's head
    for k in range(hp):
        g_row = g_ref[0, 0, pl.ds(k, 1), :]                        # [1, L]
        # the [1, L] row as an [L, 1] column, without a transpose
        g_col = jnp.sum(jnp.where(eye, g_row, 0.0), axis=1, keepdims=True)
        # exp(G_i - G_j) for j <= i; G only falls, so no exponent is positive
        m = jnp.where(seen, jnp.exp(jnp.where(seen, g_col - g_row, 0.0)),
                      0.0)
        mine = (lane >= k * P) & (lane < (k + 1) * P)
        y = jnp.where(mine, _dot(cb * m, u), y)
        g_last = jnp.min(g_row, axis=1, keepdims=True)             # [1, 1]
        eg = jnp.where(mine, jnp.exp(g_col), eg)
        left = jnp.where(mine, jnp.exp(g_last - g_col), left)
        keep = jnp.where((srow >= k * P) & (srow < (k + 1) * P),
                         jnp.exp(g_last), keep)
    s0 = s_scr[:]                                                  # [W, N]
    y_ref[0] = y + eg * _dot(c, s0, _NT)
    s1 = keep * s0 + _dot(u * left, b, _TN)
    s_scr[:] = s1

    @pl.when(ci == pl.num_programs(2) - 1)
    def _finish():
        sf_ref[0, 0] = s1


@jax.named_scope(_PALLAS_SCOPE)
def ssd_chunk_scan(u, g, b, c, state, *, chunk: int = SSD_CHUNK,
                   interpret: bool = False):
    """The shapes of :func:`ssd_scan_reference`, all f32; ``S`` is padded
    here to whole chunks with rows that stand still. Returns ``(y, final
    state)``."""
    R, S, H, P = u.shape
    N = b.shape[-1]
    hp = heads_per_step(H, P)
    W = hp * P
    L = min(chunk, -(-S // 8) * 8)
    pad = -S % L
    rows = lambda t: jnp.pad(t.astype(F32), [(0, 0), (0, pad)]
                             + [(0, 0)] * (t.ndim - 2))
    nc = (S + pad) // L
    # the log-decay summed inside each chunk, heads in front of rows
    gc = jnp.cumsum(rows(g).reshape(R, nc, L, H), axis=2)
    gc = gc.reshape(R, nc * L, H // hp, hp).transpose(0, 2, 3, 1)
    args = (rows(u).reshape(R, nc * L, H * P), rows(b), rows(c), gc,
            state.astype(F32).reshape(R, H // hp, W, N))
    row_spec = lambda width: pl.BlockSpec((1, L, width),
                                          lambda r, j, ci: (r, ci, 0))
    u_spec = pl.BlockSpec((1, L, W), lambda r, j, ci: (r, ci, j))
    s_spec = pl.BlockSpec((1, 1, W, N), lambda r, j, ci: (r, j, 0, 0))
    y, s = pl.pallas_call(
        functools.partial(_scan_kernel, hp, P),
        grid=(R, H // hp, nc),
        in_specs=[u_spec, row_spec(N), row_spec(N),
                  pl.BlockSpec((1, 1, hp, L), lambda r, j, ci: (r, j, 0, ci)),
                  s_spec],
        out_specs=[u_spec, s_spec],
        out_shape=[_out_sds((R, nc * L, H * P), F32, *args),
                   _out_sds((R, H // hp, W, N), F32, *args)],
        scratch_shapes=[pltpu.VMEM((W, N), F32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="ssd_chunk_scan",
    )(*args)
    return y[:, :S].reshape(R, S, H, P), s.reshape(R, H, P, N)


# --------------------------------------------------------------------------
# the decode step
# --------------------------------------------------------------------------

def _step_kernel(hb, s_ref, u_ref, a_ref, bc_ref, y_ref, s_out_ref):
    u = u_ref[0, 0]                                               # [hb, P]
    P = u.shape[1]
    eye = (jax.lax.broadcasted_iota(jnp.int32, (P, P), 0)
           == jax.lax.broadcasted_iota(jnp.int32, (P, P), 1)).astype(F32)
    # the inputs as columns: a product with the identity, exact in f32
    u_t = _dot(eye, u, _NT)                                       # [P, hb]
    b_row, c_row = bc_ref[0, pl.ds(0, 1), :], bc_ref[0, pl.ds(1, 1), :]
    lane = jax.lax.broadcasted_iota(jnp.int32, (P, hb), 1)
    y = jnp.zeros((P, hb), F32)
    for h in range(hb):
        s = (s_ref[0, h] * a_ref[0, 0, pl.ds(h, 1), :]
             + u_t[:, h:h + 1] * b_row)
        s_out_ref[0, h] = s
        y = jnp.where(lane == h, jnp.sum(s * c_row, axis=1, keepdims=True),
                      y)
    y_ref[0, 0] = y


def _head_block(H: int) -> int:
    return next((hb for hb in (32, 8) if H % hb == 0), H)


@jax.named_scope(_PALLAS_SCOPE)
def ssd_decode_step(state, u, decay, b, c, *, interpret: bool = False):
    """The shapes of :func:`ssd_step_reference`. ``state`` is rewritten in
    place where the caller donates it. Returns ``(y, state')``."""
    B, H, P, N = state.shape
    hb = _head_block(H)
    ng = H // hb
    wide = jnp.broadcast_to(decay.astype(F32)[..., None], (B, H, N))
    args = (state.astype(F32), u.astype(F32).reshape(B, ng, hb, P),
            wide.reshape(B, ng, hb, N),
            jnp.stack([b, c], axis=1).astype(F32))
    s_spec = pl.BlockSpec((1, hb, P, N), lambda i, j: (i, j, 0, 0))
    y, s = pl.pallas_call(
        functools.partial(_step_kernel, hb),
        grid=(B, ng),
        in_specs=[s_spec,
                  pl.BlockSpec((1, 1, hb, P), lambda i, j: (i, j, 0, 0)),
                  pl.BlockSpec((1, 1, hb, N), lambda i, j: (i, j, 0, 0)),
                  pl.BlockSpec((1, 2, N), lambda i, j: (i, 0, 0))],
        out_specs=[pl.BlockSpec((1, 1, P, hb), lambda i, j: (i, j, 0, 0)),
                   s_spec],
        out_shape=[_out_sds((B, ng, P, hb), F32, *args),
                   _out_sds(state.shape, F32, *args)],
        input_output_aliases={0: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
        name="ssd_decode_step",
    )(*args)
    return y.transpose(0, 1, 3, 2).reshape(B, H, P), s
