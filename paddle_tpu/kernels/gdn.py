"""Gated delta rule kernels (Pallas TPU): the recurrence of a Gated
DeltaNet layer, over a whole prompt and over one decode step.

Per value head, with a state ``S`` in ``R^{Dk x Dv}`` (key dim by value
dim), a token brings a key ``k`` and a query ``q`` (unit length, ``q``
scaled), a value ``v``, a log-decay ``g <= 0`` and a write strength
``beta`` in (0, 1):

    S <- exp(g) S;  r = S^T k;  u = beta (v - r);  S <- S + k u^T;  o = S^T q

A row with ``g = 0`` and ``beta = 0`` leaves the state exactly as it was:
that is how padding and idle slots stand still.

* :func:`gdn_chunk_scan` — a prompt, ``chunk`` (64) rows at a time. Inside
  a chunk the rule is a triangular system: with ``G_i`` the decay summed
  up to row ``i`` and ``A[i, j] = beta_i exp(G_i - G_j) (k_i . k_j)`` for
  ``j < i``,

      (I + A) U = beta (V - exp(G) K S0)
      O  = exp(G) Q S0 + (exp(G_i - G_j) (q_i . k_j))_{j <= i} U
      S' = exp(G_last) S0 + (exp(G_last - G) K)^T U

  so every product is a matrix product on the MXU, and only the state
  crosses chunks, in f32 VMEM scratch. ``(I + A)^-1`` is built from
  products too: the 16-row diagonal blocks ``Bd`` by
  ``(I - Bd)(I + Bd^2)(I + Bd^4)(I + Bd^8)`` (``Bd^16 = 0``), the rest by
  ``(I + N)^-1 = (I - N)(I + N^2)`` with ``N = (I + Bd)^-1 (A - Bd)``
  (``N^4 = 0``). No power past the eighth is formed, so even keys that all
  point the same way (where ``A``'s powers grow like binomials) lose four
  digits, not all of them. All of it f32 with true f32 products: the rule
  feeds itself, and a rounding of its operands is carried through every
  later row. Grid ``(sequence, value head, chunk)``, chunk innermost.
* :func:`gdn_decode_step` — one token for every slot: one pass over the
  state (read, decay, correct, write, read out), bound by the state's
  bytes. The state is updated in place (``input_output_aliases``). Grid
  ``(slot, group of heads)``.

:func:`gdn_scan_reference` and :func:`gdn_step_reference` are the same
rule in plain ``jax.numpy`` (a ``lax.scan`` over tokens): the route the
CPU takes and the oracle of the parity tests. ``interpret=True`` runs the
kernels on the CPU.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .flash_attention import _PALLAS_SCOPE, _out_sds

__all__ = ["gdn_chunk_scan", "gdn_decode_step", "gdn_scan_reference",
           "gdn_step_reference", "GDN_CHUNK"]

GDN_CHUNK = 64
_SUB = 16                 # rows of a diagonal block of the triangular system
F32 = jnp.float32
_HI = jax.lax.Precision.HIGHEST


# --------------------------------------------------------------------------
# the rule in plain jax.numpy
# --------------------------------------------------------------------------

def gdn_step_reference(state, q, k, v, decay, beta):
    """One token a sequence: ``state`` [B, Hv, Dk, Dv], ``q``/``k``
    [B, Hv, Dk], ``v`` [B, Hv, Dv], ``decay`` (= exp(g)) and ``beta``
    [B, Hv]. Returns ``(o [B, Hv, Dv], state')``."""
    s = state * decay[..., None, None]
    r = jnp.einsum("bhkv,bhk->bhv", s, k, precision=_HI)
    u = beta[..., None] * (v - r)
    s = s + k[..., :, None] * u[..., None, :]
    return jnp.einsum("bhkv,bhk->bhv", s, q, precision=_HI), s


def gdn_scan_reference(q, k, v, g, beta):
    """A token at a time from a zero state: ``q``/``k`` [R, Hk, S, Dk]
    (value head ``n`` reads key head ``n // (Hv // Hk)``), ``v``
    [R, Hv, S, Dv], ``g``/``beta`` [R, Hv, S]. Returns ``(o [R, Hv, S,
    Dv], final state [R, Hv, Dk, Dv])``."""
    R, Hv, S, Dv = v.shape
    rep = Hv // q.shape[1]
    q, k = (jnp.repeat(t, rep, axis=1) for t in (q, k))

    def one(s, t):
        qt, kt, vt, gt, bt = t
        o, s = gdn_step_reference(s, qt, kt, vt, jnp.exp(gt), bt)
        return s, o

    rows = lambda t: jnp.moveaxis(t, 2, 0)
    s, o = jax.lax.scan(one, jnp.zeros((R, Hv, q.shape[-1], Dv), F32),
                        (rows(q), rows(k), rows(v), rows(g), rows(beta)))
    return jnp.moveaxis(o, 0, 2), s


# --------------------------------------------------------------------------
# the chunked scan
# --------------------------------------------------------------------------

def _dot(a, b, dims=(((1,), (0,)), ((), ()))):
    return jax.lax.dot_general(a, b, dims, preferred_element_type=F32,
                               precision=_HI)


_NT = (((1,), (1,)), ((), ()))            # a @ b^T
_TN = (((0,), (0,)), ((), ()))            # a^T @ b


def _scan_kernel(q_ref, k_ref, v_ref, g_ref, b_ref, o_ref, sf_ref, s_scr):
    c = pl.program_id(2)

    @pl.when(c == 0)
    def _init():
        s_scr[:] = jnp.zeros_like(s_scr)

    q, k, v = q_ref[0, 0], k_ref[0, 0], v_ref[0, 0]
    C = q.shape[0]
    g_row, b_row = g_ref[0, 0, 0], b_ref[0, 0, 0]          # [1, C]
    ii = jax.lax.broadcasted_iota(jnp.int32, (C, C), 0)
    jj = jax.lax.broadcasted_iota(jnp.int32, (C, C), 1)
    eye = ii == jj
    # a [1, C] row as a [C, 1] column, without a transpose
    col = lambda row: jnp.sum(jnp.where(eye, row, 0.0), axis=1,
                              keepdims=True)
    g_col, b_col = col(g_row), col(b_row)
    seen = ii >= jj
    # exp(G_i - G_j) for j <= i; G only falls, so no exponent is positive
    decay = jnp.where(seen, jnp.exp(jnp.where(seen, g_col - g_row, 0.0)),
                      0.0)
    a = jnp.where(ii > jj, b_col * decay * _dot(k, k, _NT), 0.0)

    # (I + A)^-1, see the module's docstring
    ident = eye.astype(F32)
    bd = jnp.where(ii // _SUB == jj // _SUB, a, 0.0)
    x, p = ident - bd, bd
    for _ in range(3):
        p = _dot(p, p)
        x = x + _dot(x, p)
    n = _dot(x, a - bd)
    inv = _dot(_dot(ident - n, ident + _dot(n, n)), x)

    s0 = s_scr[:]
    eg = jnp.exp(g_col)
    u = _dot(inv, b_col * (v - eg * _dot(k, s0)))
    o_ref[0, 0] = eg * _dot(q, s0) + _dot(decay * _dot(q, k, _NT), u)
    g_last = jnp.min(g_row, axis=1, keepdims=True)           # [1, 1]
    s1 = jnp.exp(g_last) * s0 + _dot(k * jnp.exp(g_last - g_col), u, _TN)
    s_scr[:] = s1

    @pl.when(c == pl.num_programs(2) - 1)
    def _finish():
        sf_ref[0, 0] = s1


@jax.named_scope(_PALLAS_SCOPE)
def gdn_chunk_scan(q, k, v, g, beta, *, chunk: int = GDN_CHUNK,
                   interpret: bool = False):
    """The shapes of :func:`gdn_scan_reference`, all f32; ``S`` is padded
    here to whole chunks with rows that stand still. Returns ``(o, final
    state)``."""
    R, Hv, S, Dv = v.shape
    Hk, Dk = q.shape[1], q.shape[3]
    rep = Hv // Hk
    if chunk % _SUB or chunk > 4 * _SUB:
        raise ValueError(f"gdn_chunk_scan: a chunk is 1 to 4 blocks of "
                         f"{_SUB} rows (N^4 = 0 above), got {chunk}")
    C = min(chunk, -(-S // _SUB) * _SUB)
    pad = -S % C
    if pad:
        rows = lambda t, axis: jnp.pad(
            t, [(0, pad if a == axis else 0) for a in range(t.ndim)])
        q, k, v = rows(q, 2), rows(k, 2), rows(v, 2)
        g, beta = rows(g, 2), rows(beta, 2)
    nc = (S + pad) // C
    gc = jnp.cumsum(g.astype(F32).reshape(R, Hv, nc, 1, C), axis=-1)
    bc = beta.astype(F32).reshape(R, Hv, nc, 1, C)
    qk_spec = pl.BlockSpec((1, 1, C, Dk), lambda r, h, c: (r, h // rep, c, 0))
    v_spec = pl.BlockSpec((1, 1, C, Dv), lambda r, h, c: (r, h, c, 0))
    gb_spec = pl.BlockSpec((1, 1, 1, 1, C), lambda r, h, c: (r, h, c, 0, 0))
    args = (q.astype(F32), k.astype(F32), v.astype(F32), gc, bc)
    o, s = pl.pallas_call(
        _scan_kernel,
        grid=(R, Hv, nc),
        in_specs=[qk_spec, qk_spec, v_spec, gb_spec, gb_spec],
        out_specs=[v_spec, pl.BlockSpec((1, 1, Dk, Dv),
                                        lambda r, h, c: (r, h, 0, 0))],
        out_shape=[_out_sds((R, Hv, S + pad, Dv), F32, *args),
                   _out_sds((R, Hv, Dk, Dv), F32, *args)],
        scratch_shapes=[pltpu.VMEM((Dk, Dv), F32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="gdn_chunk_scan",
    )(*args)
    return o[:, :, :S], s


# --------------------------------------------------------------------------
# the decode step
# --------------------------------------------------------------------------

def _step_kernel(hb, s_ref, kq_ref, vdb_ref, o_ref, s_out_ref):
    kq = kq_ref[0, 0]                          # [2 hb, Dk]: keys, queries
    Dk = kq.shape[1]
    eye = (jax.lax.broadcasted_iota(jnp.int32, (Dk, Dk), 0)
           == jax.lax.broadcasted_iota(jnp.int32, (Dk, Dk), 1)).astype(F32)
    # the vectors as columns: a product with the identity, exact in f32
    kq_t = _dot(eye, kq, _NT)                  # [Dk, 2 hb]
    row = lambda i: vdb_ref[0, 0, pl.ds(i, 1), :]          # [1, Dv]
    for h in range(hb):
        kc, qc = kq_t[:, h:h + 1], kq_t[:, hb + h:hb + h + 1]
        s = s_ref[0, h] * row(hb + h)                       # decay
        r = jnp.sum(s * kc, axis=0, keepdims=True)
        s = s + kc * (row(2 * hb + h) * (row(h) - r))
        s_out_ref[0, h] = s
        o_ref[0, 0, pl.ds(h, 1), :] = jnp.sum(s * qc, axis=0, keepdims=True)


def _head_block(Hv: int) -> int:
    return 8 if Hv % 8 == 0 else Hv


@jax.named_scope(_PALLAS_SCOPE)
def gdn_decode_step(state, q, k, v, decay, beta, *, interpret: bool = False):
    """The shapes of :func:`gdn_step_reference`. ``state`` is rewritten in
    place where the caller donates it. Returns ``(o, state')``."""
    B, Hv, Dk, Dv = state.shape
    hb = _head_block(Hv)
    ng = Hv // hb
    wide = lambda t: jnp.broadcast_to(t.astype(F32)[..., None], (B, Hv, Dv))
    groups = lambda t, d: t.astype(F32).reshape(B, ng, hb, d)
    kq = jnp.concatenate([groups(k, Dk), groups(q, Dk)], axis=2)
    vdb = jnp.concatenate([groups(v, Dv), groups(wide(decay), Dv),
                           groups(wide(beta), Dv)], axis=2)
    s_spec = pl.BlockSpec((1, hb, Dk, Dv), lambda b, j: (b, j, 0, 0))
    o, s = pl.pallas_call(
        functools.partial(_step_kernel, hb),
        grid=(B, ng),
        in_specs=[s_spec,
                  pl.BlockSpec((1, 1, 2 * hb, Dk), lambda b, j: (b, j, 0, 0)),
                  pl.BlockSpec((1, 1, 3 * hb, Dv), lambda b, j: (b, j, 0, 0))],
        out_specs=[pl.BlockSpec((1, 1, hb, Dv), lambda b, j: (b, j, 0, 0)),
                   s_spec],
        out_shape=[_out_sds((B, ng, hb, Dv), F32, state, kq, vdb),
                   _out_sds(state.shape, F32, state, kq, vdb)],
        input_output_aliases={0: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
        name="gdn_decode_step",
    )(state.astype(F32), kq, vdb)
    return o.reshape(B, Hv, Dv), s
