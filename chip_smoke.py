"""Does the system still start on the chip?

    python3 chip_smoke.py [kernels] [bert] [gpt] [resnet] [multichip]

Drives the main path once through the entry points a user calls, at the
full width of the models the repo supports, with seeded random weights:

* kernels   — every Pallas route (flash attention forward/backward, its
              in-kernel dropout PRNG, the paged decode kernel at q_len 1
              and 8, the gated delta rule's scan and step kernels) against
              its primitive oracle on the same inputs at
              the models' own shapes, under written tolerances;
* bert      — BERT-base pretraining, bs 32 x 512, bf16 AMP, through
              ``Executor(TPUPlace()).run`` on one fixed batch;
* gpt       — GPT-2-base (768 wide, 12 layers, 12 heads) served by
              ``GenerativeEngine``: bucketed prefill, chunked prefill,
              prefix-cache hit, plain decode chunks, speculative verify;
* resnet    — ResNet-50 bs 128 bf16 training for a few steps;
* multichip — on a host with >= 4 chips: the BERT step (depth cut to 2
              layers, full width) through ``CompiledProgram
              .with_data_parallel`` and ``compile_sharded_step`` on a
              dp 2 x tp 2 mesh of the real devices, loss against the
              single-chip step. Otherwise the leg says it did not run.

No arguments runs every leg. One process; it needs the chip: without a TPU
it names the devices JAX found and exits non-zero. Any failed check or
raised leg makes the exit code non-zero and no result line is printed. On
success the last line of stdout is the result line, one JSON object with
exactly the keys ``ok`` and ``device`` (``platform``, ``kind``, ``count`` as
JAX reports them); the legs run and ``"claim": null`` are on the ``summary``
line above it. Times printed here (compile seconds per executable, plain-loop
step wall time with the fetch as the sync) are set-up facts for choosing a
measurement protocol, not metrics.
"""
from __future__ import annotations

import gc
import json
import math
import os
import sys
import time
import traceback

import numpy as np

LEGS = ("kernels", "bert", "gpt", "resnet", "multichip")

# written tolerances, set from the dtype a route COMPUTES in: operands
# rounded to bf16 (eps 2^-8) on values of magnitude up to ~4 are good to
# ~2e-2; a route whose dots run at full f32 precision on both sides is good
# to 2e-3. flash_attention asks the MXU for 'highest' on f32 inputs; the
# decode kernel leaves its f32 dots at the MXU default, which rounds the
# operands to bf16 (first chip run, PR 21: 7.8e-3 on a one-key row, i.e.
# exactly the bf16 rounding of v) — so it is held to the bf16 bound.
BF16_ATOL = 2e-2
F32_ATOL = 2e-3


class SmokeFailure(Exception):
    pass


def check(ok: bool, what: str) -> None:
    print(f"    [{'ok' if ok else 'FAIL'}] {what}", flush=True)
    if not ok:
        raise SmokeFailure(what)


def say(leg: str, msg: str) -> None:
    print(f"[{leg}] {msg}", flush=True)


def hbm(leg: str) -> None:
    import jax

    stats = jax.devices()[0].memory_stats() or {}
    if stats:
        say(leg, f"HBM in use {stats.get('bytes_in_use', 0) / 2**30:.2f} GiB, "
                 f"peak {stats.get('peak_bytes_in_use', 0) / 2**30:.2f} GiB "
                 f"of {stats.get('bytes_limit', 0) / 2**30:.2f} GiB")


class CompileLog:
    """Per-executable compile seconds (monitor on_compile hook) and the
    kernel route each program took (``kernel_route_total``)."""

    def __init__(self):
        from paddle_tpu import monitor

        self._monitor = monitor
        self.records = []
        self._hook = monitor.add_hook(on_compile=self.records.append)
        self.names = {}

    def name(self, program, name: str) -> None:
        self.names[int(program._serial)] = name

    def report(self, leg: str) -> float:
        total = 0.0
        for rec in self.records:
            secs = (rec.trace_lower_s or 0.0) + (rec.compile_s or 0.0)
            total += secs
            say(leg, f"compile {self.names.get(rec.program_serial, '?')} "
                     f"({rec.path}): trace+lower "
                     f"{rec.trace_lower_s or 0.0:.1f} s, xla "
                     f"{rec.compile_s or 0.0:.1f} s")
        say(leg, f"compile seconds, all executables: {total:.1f}")
        self.records.clear()
        return total

    def routes(self, leg: str) -> dict:
        out = {}
        for serial, key, n in route_counts():
            if serial in self.names:
                out.setdefault(self.names[serial], {})[key] = n
        for name in sorted(out):
            say(leg, f"route {name}: {out[name]}")
        return out

    def close(self) -> None:
        self._monitor.remove_hook(self._hook)


def route_counts() -> list:
    """``kernel_route_total`` as (program serial, "op:route", count)."""
    from paddle_tpu import monitor

    fam = monitor.get_registry().get("kernel_route_total")
    return [(int(labels["program"]), f"{labels['op']}:{labels['route']}",
             int(ctr.value))
            for labels, ctr in (fam.children() if fam is not None else ())]


def route_totals() -> dict:
    """The same summed over programs: {"op:route": count}."""
    out = {}
    for _, key, n in route_counts():
        out[key] = out.get(key, 0) + n
    return out


# ---------------------------------------------------------------------------
# kernels: Pallas routes against their primitive oracles, on the chip
# ---------------------------------------------------------------------------

def leg_kernels() -> dict:
    import jax
    import jax.numpy as jnp

    from paddle_tpu.kernels import (decode_attention_reference,
                                    flash_attention, flash_attention_decode)
    from paddle_tpu.lowering import LowerCtx
    from paddle_tpu.ops.fused_attention import _primitive_attention

    leg = "kernels"
    rng = np.random.RandomState(0)
    ctx = LowerCtx(platform="tpu")

    def maxerr(a, b):
        return float(jnp.max(jnp.abs(a.astype(jnp.float32)
                                     - b.astype(jnp.float32))))

    def oracle(q, k, v, bias, causal):
        # the primitive route in f32 at full precision: the truth both the
        # kernel and the primitive-at-compute-dtype are measured against
        f = jnp.float32
        return _primitive_attention(ctx, q.astype(f), k.astype(f),
                                    v.astype(f), bias, causal,
                                    q.shape[-1] ** -0.5, 0.0, True)

    # -- flash attention, BERT-base shape: bs 32 x 12 heads, S 512, D 64,
    #    bf16, key-padding bias -- forward and all three gradients
    B, H, S, D = 32, 12, 512, 64
    q, k, v = (jnp.asarray(rng.randn(B * H, S, D), jnp.bfloat16)
               for _ in range(3))
    mask = np.ones((B, S), np.float32)
    mask[::3, 400:] = 0.0                      # padded tails on some rows
    bias = jnp.asarray((mask - 1.0) * 10000.0)
    w = jnp.asarray(rng.randn(B * H, S, D), jnp.bfloat16)

    def kernel_loss(q, k, v):
        o = flash_attention(q, k, v, bias=bias, num_heads=H)
        return jnp.sum(o.astype(jnp.float32) * w.astype(jnp.float32)), o

    def oracle_loss(q, k, v):
        o = oracle(q, k, v, bias, False)
        return jnp.sum(o * w.astype(jnp.float32)), o

    (_, o_k), g_k = jax.jit(jax.value_and_grad(
        kernel_loss, argnums=(0, 1, 2), has_aux=True))(q, k, v)
    (_, o_r), g_r = jax.jit(jax.value_and_grad(
        oracle_loss, argnums=(0, 1, 2), has_aux=True))(q, k, v)
    e = maxerr(o_k, o_r)
    say(leg, f"flash fwd bf16 {B * H}x{S}x{D} + bias: max|err| {e:.2e}")
    check(bool(jnp.isfinite(o_k.astype(jnp.float32)).all()) and e <= BF16_ATOL,
          f"flash_attention forward agrees with the primitive oracle "
          f"(<= {BF16_ATOL})")
    for name, gk, gr in zip("qkv", g_k, g_r):
        scale = float(jnp.max(jnp.abs(gr.astype(jnp.float32))))
        e = maxerr(gk, gr)
        say(leg, f"flash bwd d{name}: max|err| {e:.2e} "
                 f"(max|grad| {scale:.2e})")
        check(e <= BF16_ATOL * max(scale, 1.0),
              f"flash_attention d{name} agrees with the primitive oracle "
              f"(<= {BF16_ATOL} x max|grad|)")

    # -- flash attention, GPT-2 prefill shape: 8 slots x 12 heads, S 512,
    #    f32, causal + key-padding bias
    Bg = 8
    qf, kf, vf = (jnp.asarray(rng.randn(Bg * H, S, D), jnp.float32)
                  for _ in range(3))
    gmask = np.ones((Bg, S), np.float32)
    gmask[1::2, 300:] = 0.0
    gbias = jnp.asarray((gmask - 1.0) * 10000.0)
    o_k = jax.jit(lambda q, k, v: flash_attention(
        q, k, v, bias=gbias, causal=True, num_heads=H))(qf, kf, vf)
    o_r = jax.jit(lambda q, k, v: oracle(q, k, v, gbias, True))(qf, kf, vf)
    e = maxerr(o_k, o_r)
    say(leg, f"flash fwd f32 causal {Bg * H}x{S}x{D} + bias: "
             f"max|err| {e:.2e}")
    check(e <= F32_ATOL, f"causal flash_attention (GPT prefill shape) "
                         f"agrees with the primitive oracle (<= {F32_ATOL})")

    # -- in-kernel dropout PRNG (no interpret mode: only a chip runs it).
    #    q = k = 0 makes p uniform, so o = sum_j keep_ij v_j / ((1-r) S):
    #    with v = 1 every row is the row's keep count over its expectation
    rate = 0.1
    zeros = jnp.zeros((B * H, S, D), jnp.float32)
    ones = jnp.ones((B * H, S, D), jnp.float32)

    @jax.jit
    def drop(q, k, v, seed):
        return flash_attention(q, k, v, dropout_rate=rate, seed=seed,
                               num_heads=H)

    o1 = drop(zeros, zeros, ones, 7)
    rows = np.asarray(o1[:, :, 0], np.float64)
    want_std = math.sqrt(rate / ((1.0 - rate) * S))
    say(leg, f"dropout {rate}: row mean {rows.mean():.5f} (want 1), row "
             f"std {rows.std():.5f} (want {want_std:.5f})")
    check(abs(rows.mean() - 1.0) < 2e-3
          and 0.7 * want_std < rows.std() < 1.3 * want_std,
          "in-kernel dropout keeps 1-rate of the probabilities, "
          "independently per element")
    check(bool(jnp.array_equal(o1, drop(zeros, zeros, ones, 7)))
          and not bool(jnp.array_equal(o1, drop(zeros, zeros, ones, 8))),
          "dropout mask is a function of the seed (same seed -> same bits)")
    # backward regenerates the SAME mask. o is linear in v, so with the
    # mask fixed <dL/dv, v> == L exactly (dK/dV kernel); along a direction
    # u, dL/dq . u must match a central difference of L (dQ kernel)
    wv = jnp.asarray(rng.randn(B * H, S, D), jnp.float32)
    qd, kd, vd = (jnp.asarray(0.5 * rng.randn(B * H, S, D), jnp.float32)
                  for _ in range(3))

    @jax.jit
    def dloss(q, k, v):
        return jnp.sum(drop(q, k, v, 11) * wv)

    L, (dq, _dk, dv) = jax.jit(jax.value_and_grad(
        dloss, argnums=(0, 1, 2)))(qd, kd, vd)
    euler = float(jnp.sum(dv * vd))
    say(leg, f"dropout bwd: L {float(L):.4f}, <dL/dv, v> {euler:.4f}")
    check(abs(euler - float(L)) <= 1e-3 * max(abs(float(L)), 1.0),
          "dK/dV kernel regenerates the forward dropout mask "
          "(<dL/dv, v> == L)")
    u = jnp.asarray(rng.randn(B * H, S, D), jnp.float32)
    eps = 1e-2
    fd = (float(dloss(qd + eps * u, kd, vd))
          - float(dloss(qd - eps * u, kd, vd))) / (2 * eps)
    an = float(jnp.sum(dq * u))
    say(leg, f"dropout bwd: dL/dq.u analytic {an:.4f}, central "
             f"difference {fd:.4f}")
    check(abs(an - fd) <= 3e-2 * max(abs(fd), 1.0),
          "dQ kernel regenerates the forward dropout mask "
          "(directional derivative matches a central difference)")

    # -- paged decode kernel, GPT-2 shape: 8 slots x 12 heads, cache 1024,
    #    page 128, f32; q_len 1 (decode) and 8 (speculative verify)
    S_max, page = 1024, 128
    kc, vc = (jnp.asarray(rng.randn(Bg * H, S_max, D), jnp.float32)
              for _ in range(2))
    lengths = jnp.asarray([1, 127, 128, 129, 500, 777, 1000, 1016],
                          jnp.int32)
    for q_len in (1, 8):
        qc = jnp.asarray(rng.randn(Bg * H, q_len, D), jnp.float32)
        o_k = jax.jit(lambda q, k, v, l: flash_attention_decode(
            q, k, v, l, num_heads=H, page_size=page))(qc, kc, vc, lengths)
        o_r = jax.jit(lambda q, k, v, l: decode_attention_reference(
            q, k, v, jnp.repeat(l, H), D ** -0.5))(qc, kc, vc, lengths)
        e = maxerr(o_k, o_r)
        say(leg, f"decode kernel f32 q_len {q_len} {Bg * H}x{S_max}x{D} "
                 f"page {page}: max|err| {e:.2e}")
        check(e <= BF16_ATOL,
              f"flash_attention_decode q_len={q_len} agrees with "
              f"decode_attention_reference (<= {BF16_ATOL}, bf16-rounded "
              f"operands)")
    # -- the append in the view the kernel reads heads of 64 in
    #    (kernels.rows_minor): the kernel that writes columns of [slots,
    #    heads, D, rows] in place against the row append on the declared
    #    shape, a chunk of 8 rows, one slot masked out, one across a block
    #    edge (row 127 on) and two clamped onto the last row
    from paddle_tpu.kernels import (kv_append, paged_kv_append,
                                    paged_kv_append_rows, rows_minor)
    check(rows_minor(D, jnp.float32, page),
          "a cache of 64-wide heads in pages of 128 is read rows-minor")
    c4 = kc.reshape(Bg, H, S_max, D)
    new = jnp.asarray(rng.randn(Bg, H, 8, D), jnp.float32)
    keep = jnp.asarray([1, 1, 0, 1, 1, 1, 1, 1], jnp.float32)
    by_rows = jax.jit(paged_kv_append_rows)(c4, new, lengths + 6, keep)
    by_cols = jax.jit(lambda c, n, p, m: kv_append(
        c.swapaxes(2, 3), n, p, m).swapaxes(2, 3))(c4, new, lengths + 6, keep)
    check(bool(jnp.array_equal(by_rows, by_cols))
          and bool(jnp.array_equal(by_cols[2], c4[2]))
          and not bool(jnp.array_equal(by_cols[0], c4[0])),
          "kv_append writes what the row append writes, bit for bit, and "
          "leaves a masked-out slot's cache as it was")
    # -- rows that are whole lane tiles (heads of 128, bf16) lie as declared
    #    and go in by ONE scatter a cache (PR 48), against the loop over the
    #    slots that wrote them a row at a time: 4 rows a slot, slot 2
    #    masked out, the last slot's tail clamped onto the last row
    c128 = jnp.asarray(rng.randn(Bg, 4, S_max, 128), jnp.bfloat16)
    n128 = jnp.asarray(rng.randn(Bg, 4, 4, 128), jnp.bfloat16)

    @jax.jit
    def row_by_row(c, n, p, m):
        for i in range(n.shape[2]):
            c = paged_kv_append(c, n[:, :, i:i + 1],
                                jnp.minimum(p + i, S_max - 1), m)
        return c

    scattered = jax.jit(paged_kv_append_rows)(c128, n128, lengths + 6, keep)
    check(bool(jnp.array_equal(scattered,
                               row_by_row(c128, n128, lengths + 6, keep)))
          and bool(jnp.array_equal(scattered[2], c128[2]))
          and not bool(jnp.array_equal(scattered[0], c128[0])),
          "paged_kv_append_rows' one scatter writes what the loop over the "
          "slots writes, bit for bit, and leaves a masked-out slot's cache "
          "as it was")
    # -- a step of one row: the decode kernel merges the column into the
    #    last live block it fetches and writes that block back (PR 45),
    #    against `kv_append` and then the kernel: the attention and both
    #    caches bit for bit, slot 2 masked out
    one, q1 = new[:, :, :1], jnp.asarray(rng.randn(Bg * H, 1, D), jnp.float32)
    v4 = vc.reshape(Bg, H, S_max, D)

    @jax.jit
    def append_then_attend(q, ck, cv, n, at, m):
        ck, cv = (kv_append(c.swapaxes(2, 3), n, at, m).swapaxes(2, 3)
                  for c in (ck, cv))
        return flash_attention_decode(
            q, ck.reshape(kc.shape), cv.reshape(kc.shape), at + 1,
            num_heads=H, page_size=page), ck, cv

    @jax.jit
    def append_in_kernel(q, ck, cv, n, at, m):
        o, ck2, cv2 = flash_attention_decode(
            q, ck.reshape(kc.shape), cv.reshape(kc.shape), at + 1,
            num_heads=H, page_size=page, append=(n, n, m))
        return o, ck2.reshape(ck.shape), cv2.reshape(cv.shape)

    two = append_then_attend(q1, c4, v4, one, lengths - 1, keep)
    fused = append_in_kernel(q1, c4, v4, one, lengths - 1, keep)
    check(all(bool(jnp.array_equal(a, b)) for a, b in zip(two, fused))
          and bool(jnp.array_equal(fused[1][2], c4[2]))
          and not bool(jnp.array_equal(fused[1][0], c4[0])),
          "flash_attention_decode(append=...) returns what kv_append and "
          "then the kernel return, bit for bit, a masked-out slot untouched")
    # -- gated delta rule (kernels/gdn.py): the chunked scan over two
    #    prompts in a 640-row bucket (one of 500 real rows) and then the
    #    decode step, 16 key / 32 value heads of 128 x 128, f32, against the
    #    token loop in plain jax.numpy
    from paddle_tpu.kernels.gdn import (gdn_chunk_scan, gdn_decode_step,
                                        gdn_scan_reference,
                                        gdn_step_reference)

    R, Hk, Hv, S, Dh = 2, 16, 32, 640, 128
    unit = lambda t: t / jnp.linalg.norm(t, axis=-1, keepdims=True)
    f32 = lambda *shape: jnp.asarray(rng.randn(*shape), jnp.float32)
    live = (jnp.arange(S)[None] < jnp.asarray([S, 500])[:, None])[:, None]
    qg, kg = unit(f32(R, Hk, S, Dh)) * Dh ** -0.5, unit(f32(R, Hk, S, Dh))
    vg = f32(R, Hv, S, Dh)
    gg = -0.3 * jnp.exp(f32(R, Hv, S)) * live
    bg = jax.nn.sigmoid(f32(R, Hv, S)) * live
    o_k, s_k = jax.jit(gdn_chunk_scan)(qg, kg, vg, gg, bg)
    o_r, s_r = jax.jit(gdn_scan_reference)(qg, kg, vg, gg, bg)
    e = max(maxerr(o_k[0], o_r[0]), maxerr(o_k[1, :, :500], o_r[1, :, :500]),
            maxerr(s_k, s_r))
    say(leg, f"gdn_chunk_scan {R}x{Hv}x{S}x{Dh}: max|err| {e:.2e}")
    check(e <= 1e-4, "gdn_chunk_scan agrees with the token loop (<= 1e-4, "
                     "f32 in another order)")
    rep = lambda t: jnp.repeat(t, Hv // Hk, axis=1)
    args = (s_r, rep(qg[:, :, 7]), rep(kg[:, :, 7]), vg[:, :, 7],
            jnp.exp(gg[:, :, 7]), bg[:, :, 7])
    o_k, s_k = jax.jit(gdn_decode_step)(*args)
    o_r, s_r = jax.jit(gdn_step_reference)(*args)
    e = max(maxerr(o_k, o_r), maxerr(s_k, s_r))
    say(leg, f"gdn_decode_step {R}x{Hv}x{Dh}x{Dh}: max|err| {e:.2e}")
    check(e <= 1e-4, "gdn_decode_step agrees with one step of the token "
                     "loop (<= 1e-4)")
    # -- Mamba-2 selective scan (kernels/ssd.py): the chunked scan over two
    #    prompts in a 768-row bucket (one of 500 real rows) from a carried
    #    state and then the decode step, 128 heads of 64 over a state of 128,
    #    f32, against the token loop in plain jax.numpy
    from paddle_tpu.kernels.ssd import (ssd_chunk_scan, ssd_decode_step,
                                        ssd_scan_reference,
                                        ssd_step_reference)

    R, Hm, S, Pm, Nm = 2, 128, 768, 64, 128
    live = (jnp.arange(S)[None] < jnp.asarray([S, 500])[:, None])[..., None]
    um = 0.3 * f32(R, S, Hm, Pm) * live[..., None]
    gm = -jnp.exp(f32(R, S, Hm) - 2.0) * live
    bm, cm, s0 = f32(R, S, Nm), f32(R, S, Nm), f32(R, Hm, Pm, Nm)
    y_k, s_k = jax.jit(ssd_chunk_scan)(um, gm, bm, cm, s0)
    y_r, s_r = jax.jit(ssd_scan_reference)(um, gm, bm, cm, s0)
    e = max(maxerr(y_k[0], y_r[0]), maxerr(y_k[1, :500], y_r[1, :500]),
            maxerr(s_k, s_r))
    say(leg, f"ssd_chunk_scan {R}x{S}x{Hm}x{Pm}x{Nm}: max|err| {e:.2e}")
    check(e <= 5e-4, "ssd_chunk_scan agrees with the token loop (<= 5e-4 on "
                     "sums of 128 products of order 10, f32 in another "
                     "order)")
    args = (s_r, um[:, 7], jnp.exp(gm[:, 7]), bm[:, 7], cm[:, 7])
    y_k, s_k = jax.jit(ssd_decode_step)(*args)
    y_r, s_r = jax.jit(ssd_step_reference)(*args)
    e = max(maxerr(y_k, y_r), maxerr(s_k, s_r))
    say(leg, f"ssd_decode_step {R}x{Hm}x{Pm}x{Nm}: max|err| {e:.2e}")
    check(e <= 5e-4, "ssd_decode_step agrees with one step of the token "
                     "loop (<= 5e-4)")
    # -- latent attention (kernels/latent_attention.py): the decode kernel
    #    over a latent cache at the published widths, 20 heads on rows of
    #    512 + 64 in 640 lanes, bf16, 4,096 rows in blocks of 1,024: lengths
    #    at a block's end, across one, one row, the full cache, and a slot
    #    that sees nothing
    from paddle_tpu.kernels.latent_attention import (
        latent_block_rows, latent_row_width, mla_decode_attention,
        mla_decode_attention_reference)

    Bl, Hl, Sl, dc, dr = 6, 20, 4096, 512, 64
    Wl = latent_row_width(dc, dr)
    used = jnp.arange(Wl) < dc + dr
    bf = lambda *shape: jnp.where(
        used, jnp.asarray(rng.randn(*shape) * 0.5, jnp.bfloat16), 0)
    ql, cl = bf(Bl, Hl, Wl), bf(Bl, Sl, Wl)
    lens = jnp.asarray([1024, 1025, 1, 4096, 0, 2500], jnp.int32)
    check(Wl == 640 and latent_block_rows(Sl, Wl, jnp.bfloat16, 128) == 1024,
          "a latent cache of 512 + 64 lies in 640 lanes and is walked in "
          "blocks of 1,024 rows")
    u_k = jax.jit(lambda *a: mla_decode_attention(
        *a, latent_dim=dc, scale=1 / 16, page_size=128))(ql, cl, lens)
    u_r = jax.jit(lambda *a: mla_decode_attention_reference(
        *a, dc, 1 / 16))(ql, cl, lens)
    e = maxerr(u_k.astype(jnp.float32), u_r.astype(jnp.float32))
    say(leg, f"mla_decode_attention {Bl}x{Hl}x{Sl}x({dc}+{dr}) bf16: "
             f"max|err| {e:.2e}")
    check(e <= 2e-2 and not bool(jnp.any(u_k[4])),
          "mla_decode_attention agrees with its reference (<= 2e-2, bf16 "
          "probabilities against f32), and a slot that sees no key gives 0")
    # -- hyper-connections (kernels/hyper_connection.py): the read and the
    #    write of four f32 streams of 3,584 over a decode step's 256 rows,
    #    each one pass over a tile of 128 rows, against the equations
    from paddle_tpu.kernels.hyper_connection import (
        hc_read, hc_read_reference, hc_write, hc_write_reference)

    nh_, Ch, Rh = 4, 3584, 256
    f32 = lambda a: jnp.asarray(a, jnp.float32)
    bias_h = rng.uniform(-1, 1, nh_ * (nh_ + 2))
    bias_h[2 * nh_:] += 4 * np.eye(nh_).ravel()
    xh, yh = f32(rng.randn(Rh, nh_ * Ch)), f32(rng.randn(Rh, Ch))
    ph = f32(rng.randn(nh_ * (nh_ + 2), nh_ * Ch) * 0.02)
    ah, bh = f32(rng.uniform(0.5, 1.5, 3)), f32(bias_h)
    u_k, c_k, _ = jax.jit(lambda *a: hc_read(*a, n=nh_))(xh, ph, ah, bh)
    u_r, c_r, _ = jax.jit(lambda *a: hc_read_reference(*a, n=nh_))(
        xh, ph, ah, bh)
    post, res = c_r[:, nh_:2 * nh_], c_r[:, 2 * nh_:]
    o_k = jax.jit(lambda *a: hc_write(*a, n=nh_))(xh, yh, post, res)
    o_r = jax.jit(lambda *a: hc_write_reference(*a, n=nh_))(xh, yh, post,
                                                            res)
    e = max(maxerr(u_k, u_r), maxerr(c_k[:, :c_r.shape[1]], c_r),
            maxerr(o_k, o_r))
    say(leg, f"hc_read / hc_write {Rh}x{nh_}x{Ch} f32: max|err| {e:.2e}")
    check(e <= 1e-4, "the hyper-connection kernels agree with the "
                     "equations (<= 1e-4: true f32 products in another "
                     "order)")
    hbm(leg)
    return {}


# ---------------------------------------------------------------------------
# bert: the trainer
# ---------------------------------------------------------------------------

def leg_bert(cfg=None, batch: int = 32, seq_len: int = 512) -> dict:
    import jax

    import paddle_tpu as fluid
    import paddle_tpu.unique_name as un
    from paddle_tpu.models.bert import (BertConfig, build_bert_pretrain,
                                        synthetic_pretrain_batch)

    leg, steps = "bert", 12
    log = CompileLog()
    cfg = cfg or BertConfig.base()
    with un.guard():
        model = build_bert_pretrain(cfg, seq_len=seq_len, amp=True)
    log.name(model["main"], "bert.main")
    log.name(model["startup"], "bert.startup")
    exe = fluid.Executor(fluid.TPUPlace())
    scope = fluid.Scope()
    feed = synthetic_pretrain_batch(cfg, batch, seq_len)
    losses, walls = [], []
    with fluid.scope_guard(scope):
        exe.run(model["startup"])
        for _ in range(steps):
            t0 = time.perf_counter()
            (lv,) = exe.run(model["main"], feed=feed,
                            fetch_list=[model["loss"]])
            walls.append(time.perf_counter() - t0)
            losses.append(float(np.asarray(lv).reshape(-1)[0]))
    say(leg, f"BERT-base bs {batch} x {seq_len} bf16, {cfg.num_layers} "
             f"layers: losses {[round(l, 3) for l in losses]}")
    say(leg, f"plain-loop step wall (host clock, fetch is the sync): first "
             f"{walls[0]:.1f} s (compile), steady median "
             f"{1e3 * float(np.median(walls[2:])):.1f} ms over "
             f"{len(walls) - 2} steps")
    log.report(leg)
    routes = log.routes(leg)
    log.close()
    want0 = math.log(cfg.vocab_size) + math.log(2.0)
    check(all(np.isfinite(losses)), "loss finite on every step")
    check(abs(losses[0] - want0) < 0.5,
          f"first loss {losses[0]:.3f} near ln(vocab) + ln 2 = {want0:.3f}")
    check(losses[-1] < losses[0] - 0.1,
          f"loss fell over {steps} Adam steps on one batch "
          f"({losses[0]:.3f} -> {losses[-1]:.3f})")
    check(set(routes.get("bert.main", {})) ==
          {"fused_multihead_attention:pallas",
           "fused_multihead_attention_grad:pallas"},
          "every attention op of the train step took the Pallas route, and "
          "every gradient op rode the forward's saved residuals")
    tpu = exe.place.jax_device()
    homes = {d for v in scope.vars.values() if isinstance(v, jax.Array)
             for d in v.devices()}
    check(homes == {tpu}, f"all {len(scope.vars)} persistables live on {tpu}")
    hbm(leg)
    return {"first_loss": losses[0]}


# ---------------------------------------------------------------------------
# gpt: the server
# ---------------------------------------------------------------------------

def leg_gpt(cfg=None, slots: int = 8, max_seq: int = 1024, page: int = 128,
            buckets=(128, 512), spec_k: int = 8) -> dict:
    import paddle_tpu as fluid
    import paddle_tpu.unique_name as un
    from paddle_tpu import serving
    from paddle_tpu.models.gpt import GptConfig, build_gpt_generative

    leg = "gpt"
    log = CompileLog()
    cfg = cfg or GptConfig.base()
    with un.guard():
        net = build_gpt_generative(cfg, batch_slots=slots, max_seq=max_seq,
                                   page_size=page, prompt_buckets=buckets,
                                   spec_k=spec_k)
    log.name(net["startup"], "gpt.startup")
    for b in buckets:
        log.name(net["prefill"][b]["main"], f"gpt.prefill:{b}")
    log.name(net["decode"]["main"], "gpt.decode")
    log.name(net["chunk"]["main"], f"gpt.chunk:{net['prefill_chunk']}")
    log.name(net["verify"]["main"], f"gpt.verify:{spec_k}")
    exe = fluid.Executor(fluid.TPUPlace())
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe.run(net["startup"], scope=scope)

    def engine(speculative: bool):
        return serving.GenerativeEngine(
            net, scope=scope, executor=exe,
            config=serving.ServingConfig(max_batch=slots, queue_depth=64,
                                         deadline_s=0),
            gen_config=serving.GenerationConfig(decode_chunk=4,
                                                speculative=speculative))

    rng = np.random.RandomState(5)

    def prompt(n):
        return rng.randint(1, cfg.vocab_size, n).astype(np.int64)

    def answered(what, out, max_new):
        out = np.asarray(out)
        check(out.shape == (max_new,) and int(out.min()) >= 0
              and int(out.max()) < cfg.vocab_size,
              f"{what}: {max_new} tokens, all inside the vocabulary")

    rows = {net["prefill"][b]["rows"] for b in buckets}
    say(leg, f"GPT-2-base {cfg.hidden_size} wide x {cfg.num_layers} layers "
             f"x {cfg.num_heads} heads, {slots} slots x max_seq {max_seq}, "
             f"page {page}, buckets {buckets}, spec_k {spec_k}, "
             f"{sorted(rows)} sequences a bucket prefill")
    check(max(rows) < slots,
          "a bucket prefill carries fewer sequences than there are slots, "
          "each naming its slot")
    eng = engine(False)
    t0 = time.perf_counter()
    n_exec = eng.warm_up()
    say(leg, f"warm_up: {n_exec} executables in "
             f"{time.perf_counter() - t0:.1f} s")
    cold_compile = log.report(leg)
    shared = prompt(3 * page)                  # three whole prefix pages
    walls = {}
    with eng:
        def ask(what, p, max_new):
            t0 = time.perf_counter()
            out = eng.submit(p, max_new_tokens=max_new).result(
                timeout=600)[0]
            walls[what] = time.perf_counter() - t0
            answered(what, out, max_new)

        small, big = buckets
        long = big + big // 2 - page // 2      # 5.5 pages at the defaults
        ask(f"bucket {small} prefill (prompt {small - page // 4})",
            prompt(small - page // 4), 12)
        ask(f"bucket {big} prefill (prompt 3 pages + {page // 8}, "
            f"publishes 3 pages)",
            np.concatenate([shared, prompt(page // 8)]), 8)
        ask(f"chunked prefill (prompt {long} > largest bucket)",
            prompt(long), 8)
        ask("prefix hit (same 3 pages + another suffix)",
            np.concatenate([shared, prompt(page // 3)]), 8)
        futs = [eng.submit(prompt(n), max_new_tokens=16)
                for n in (small // 6, small // 3, small // 2)]
        for i, f in enumerate(futs):
            answered(f"concurrent stream {i}", f.result(timeout=600)[0], 16)
    stats, acct = eng.generation_stats(), eng.accounting()
    for what, dt in walls.items():
        say(leg, f"request wall {dt:.2f} s — {what}")
    say(leg, f"plain engine stats: {json.dumps(stats, default=str)}")
    check(acct["exact"], "plain engine: accounting exact")
    check(stats["decode_recompiles"] == 0, "plain engine: zero warm "
                                           "recompiles")
    check(stats["prefix_cache"]["hits"] >= 1
          and stats["prefix_cache"]["pages_reused"] >= 3,
          "prefix cache: the shared 3 pages were copied in, not re-prefilled")
    check(stats["prefill_chunks"] >= -(-long // page) + 1,
          "chunked prefill: the over-bucket prompt and the prefix-hit suffix "
          "went through chunk slices")
    check({f"prefill:{b}" for b in buckets} | {f"decode:{slots}",
          f"chunk:{net['prefill_chunk']}"} <= set(stats["compiled_buckets"]),
          "every plain-path executable was taken")

    spec = engine(True)
    spec.warm_up()
    cold_compile += log.report(leg)
    with spec:
        # a prompt that repeats itself gives the n-gram drafter material
        rep = np.tile(prompt(small // 5), 4)
        t0 = time.perf_counter()
        out = spec.submit(rep, max_new_tokens=32).result(timeout=600)[0]
        say(leg, f"request wall {time.perf_counter() - t0:.2f} s — "
                 f"speculative, prompt {len(rep)}, 32 new tokens")
        answered("speculative stream", out, 32)
    sstats = spec.generation_stats()
    say(leg, f"speculative engine stats: "
             f"{json.dumps(sstats['speculative'])}")
    check(spec.accounting()["exact"], "speculative engine: accounting exact")
    check(sstats["decode_recompiles"] == 0,
          "speculative engine: zero warm recompiles")
    check(sstats["speculative"]["chunks"] >= 1
          and f"verify:{spec_k}" in sstats["compiled_buckets"],
          "speculative pass dispatched verify chunks")
    routes = log.routes(leg)
    log.close()
    want = {f"gpt.prefill:{b}": {"fused_multihead_attention:pallas"}
            for b in buckets}
    # a kernel-routed verify chunk appends through ``kv_append`` (PR 34),
    # a decode step of one row inside the decode kernel (PR 45): each
    # counts its own lowerings beside the attention's
    want[f"gpt.verify:{spec_k}"] = {
        "fused_decode_attention:pallas", "kv_append:pallas"}
    want["gpt.decode"] = {
        "fused_decode_attention:pallas",
        "fused_decode_attention.append_in_kernel:pallas"}
    # a 128-row chunk is past the decode kernel's 8-row tile: the chunk
    # program rides the primitive path, and says so
    want[f"gpt.chunk:{net['prefill_chunk']}"] = {
        "fused_decode_attention:primitive"}
    check({n: set(r) for n, r in routes.items()} == want,
          "attention routes: prefill/decode/verify Pallas, chunk primitive")
    hbm(leg)
    return {"cold_compile_s": cold_compile}


# ---------------------------------------------------------------------------
# resnet
# ---------------------------------------------------------------------------

def leg_resnet() -> dict:
    import paddle_tpu as fluid
    import paddle_tpu.unique_name as un
    from paddle_tpu.models.resnet import build_resnet

    leg, batch, steps = "resnet", 128, 6
    log = CompileLog()
    with un.guard():
        model = build_resnet(depth=50, class_num=1000, amp=True)
    log.name(model["main"], "resnet50.main")
    log.name(model["startup"], "resnet50.startup")
    exe = fluid.Executor(fluid.TPUPlace())
    scope = fluid.Scope()
    rng = np.random.RandomState(0)
    feed = {"img": rng.rand(batch, 3, 224, 224).astype(np.float32),
            "label": rng.randint(0, 1000, (batch, 1)).astype(np.int64)}
    losses, walls = [], []
    with fluid.scope_guard(scope):
        exe.run(model["startup"])
        for _ in range(steps):
            t0 = time.perf_counter()
            (lv,) = exe.run(model["main"], feed=feed,
                            fetch_list=[model["loss"]])
            walls.append(time.perf_counter() - t0)
            losses.append(float(np.asarray(lv).reshape(-1)[0]))
    say(leg, f"ResNet-50 bs {batch} bf16: losses "
             f"{[round(l, 3) for l in losses]}")
    say(leg, f"plain-loop step wall (host clock, fetch is the sync, 77 MB "
             f"host feed per step): first {walls[0]:.1f} s (compile), steady "
             f"median {1e3 * float(np.median(walls[2:])):.1f} ms")
    log.report(leg)
    log.close()
    check(all(np.isfinite(losses)), "loss finite on every step")
    check(abs(losses[0] - math.log(1000.0)) < 1.5,
          f"first loss {losses[0]:.3f} near ln(1000) = "
          f"{math.log(1000.0):.3f}")
    hbm(leg)
    return {}


# ---------------------------------------------------------------------------
# multichip
# ---------------------------------------------------------------------------

def leg_multichip() -> dict:
    import jax

    import paddle_tpu as fluid
    import paddle_tpu.unique_name as un
    from __graft_entry__ import sharded_bert_step
    from paddle_tpu.models.bert import (BertConfig, build_bert_pretrain,
                                        synthetic_pretrain_batch)
    from paddle_tpu.parallel.sharding import make_mesh

    leg = "multichip"
    devices = jax.devices()
    if len(devices) < 4:
        say(leg, f"NOT RUN: the multichip leg needs >= 4 devices, this host "
                 f"has {len(devices)}")
        return {"ran": False}
    devices = devices[:4]
    # full width, depth cut to 2 layers, dropout off: three compiles of the
    # step fit the time limit, and the loss is a function of the weights
    # and the batch alone, so the three paths must agree on it
    cfg = BertConfig.base()
    cfg.num_layers = 2
    batch, seq_len, tol = 32, 512, 0.05
    feed = synthetic_pretrain_batch(cfg, batch, seq_len)
    routes_before = route_totals()

    def build():
        with un.guard():
            return build_bert_pretrain(cfg, seq_len=seq_len, lr=1e-3,
                                       amp=True, is_test=True)

    def run(compiled_of):
        model = build()
        exe = fluid.Executor(fluid.TPUPlace())
        scope = fluid.Scope()
        with fluid.scope_guard(scope):
            exe.run(model["startup"])
            prog = compiled_of(model)
            out = [float(np.asarray(exe.run(
                prog, feed=feed, fetch_list=[model["loss"]])[0]).reshape(-1)[0])
                for _ in range(2)]
        return out, scope

    single, _ = run(lambda m: m["main"])
    say(leg, f"single chip, 2 steps: {single}")
    dp_losses, dp_scope = run(
        lambda m: fluid.CompiledProgram(m["main"]).with_data_parallel(
            loss_name=m["loss"].name, places=devices))
    say(leg, f"CompiledProgram.with_data_parallel over 4 chips: {dp_losses}")
    w = dp_scope.find_var("word_embedding")
    check({s.device for s in w.addressable_shards} == set(devices),
          "data-parallel state sits on four distinct devices")
    check(all(abs(a - b) <= tol for a, b in zip(dp_losses, single)),
          f"data-parallel losses match the single-chip step (<= {tol})")

    mesh = make_mesh({"dp": 2, "tp": 2}, devices)
    tp_losses, state = sharded_bert_step(mesh, cfg, seq_len, batch, amp=True,
                                         is_test=True, steps=2)
    say(leg, f"compile_sharded_step on dp 2 x tp 2: {tp_losses}")
    ffn = state["layer0_ffn1_w"]
    shard_shapes = {tuple(s.data.shape) for s in ffn.addressable_shards}
    check({s.device for s in ffn.addressable_shards} == set(devices)
          and shard_shapes == {(cfg.hidden_size, cfg.intermediate_size // 2)},
          "tensor-parallel FFN weight is split over tp on four distinct "
          "devices")
    check(all(abs(a - b) <= tol for a, b in zip(tp_losses, single)),
          f"dp x tp losses match the single-chip step (<= {tol})")
    routes = {k: n - routes_before.get(k, 0)
              for k, n in route_totals().items()
              if n > routes_before.get(k, 0)}
    say(leg, f"routes taken in this leg: {routes}")
    check(set(routes) == {"fused_multihead_attention:pallas",
                          "fused_multihead_attention_grad:pallas"},
          "on the meshes too every attention op and its gradient took the "
          "Pallas route (per shard, under shard_map)")
    return {"ran": True}


# ---------------------------------------------------------------------------

def result_line(devices) -> str:
    """The last line of stdout on success: exactly these keys, the device
    as JAX reports it. Anything else the run has to say goes above it."""
    return json.dumps({
        "ok": True,
        "device": {"platform": devices[0].platform,
                   "kind": devices[0].device_kind, "count": len(devices)},
    })


def main(argv) -> int:
    legs = list(argv) or list(LEGS)
    unknown = [leg for leg in legs if leg not in LEGS]
    if unknown:
        print(f"chip_smoke: unknown leg(s) {unknown}; known: {LEGS}",
              file=sys.stderr)
        return 1

    import jax
    import jaxlib

    from paddle_tpu.compile_cache import enable_compile_cache

    cache_dir = enable_compile_cache()
    devices = jax.devices()
    dev = devices[0]
    try:
        from importlib.metadata import version

        libtpu = version("libtpu")
    except Exception:
        libtpu = "not installed"
    print(f"jax {jax.__version__}, jaxlib {jaxlib.__version__}, "
          f"libtpu {libtpu}")
    print(f"device: platform {dev.platform}, kind {dev.device_kind}, "
          f"count {len(devices)}")
    print(f"compile cache: {cache_dir} "
          f"({len(os.listdir(cache_dir)) if os.path.isdir(cache_dir) else 0}"
          f" entries at start)", flush=True)
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {devices} "
              f"(JAX_PLATFORMS={os.environ.get('JAX_PLATFORMS')!r})",
              file=sys.stderr)
        return 1

    failed, facts = [], {}
    t_all = time.perf_counter()
    for leg in legs:
        print(f"== {leg}", flush=True)
        t0 = time.perf_counter()
        try:
            facts[leg] = globals()[f"leg_{leg}"]()
        except Exception:
            # a failed leg fails the run (exit code below); the others
            # still run so one chip call reports everything it can
            traceback.print_exc()
            failed.append(leg)
        say(leg, f"leg wall {time.perf_counter() - t0:.1f} s")
        gc.collect()
    print(f"chip_smoke wall {time.perf_counter() - t_all:.1f} s, legs "
          f"{legs}, failed {failed}", flush=True)
    if failed:
        print(f"chip_smoke: FAILED legs: {failed}", file=sys.stderr)
        return 1
    print("summary " + json.dumps({
        "legs": legs,
        "multichip_ran": bool(facts.get("multichip", {}).get("ran")),
        "claim": None,
    }))
    print(result_line(devices), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
