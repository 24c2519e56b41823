"""GPT: causal decoder-only transformer for generative serving.

A pre-LN GPT-2-style decoder expressed as fluid Programs. The block is
written once (:func:`_block`: LayerNorm with bias, biased projections,
learned positions, f32 caches) over an ``attend`` handle, and the phases
are built from it over one shared weight set (feeds, state table, commits
and handles are ``models/decoder.py``'s):

* **prefill** (:func:`build_gpt_prefill`) — full-sequence causal forward
  over a padded prompt bucket, on the sequences it seats only: each row
  names its slot, bulk-writes every layer's K/V for the whole prompt into
  that slot's paged caches, samples the FIRST generated token from the
  last real position and commits the slot's generation state, so a refill
  touches only the slots being prefilled while their neighbours decode.
* **decode** (:func:`build_gpt_decode`) — one token for every sequence in
  the batch, at per-sequence positions. No feeds: token, position and
  caches are persistable state, which is what lets a whole decode chunk
  run as ONE ``run_chained`` scan with the caches donated through the
  carry; sampling happens in-program, so no host round-trip separates
  tokens.
* **chunk** and **verify** (:func:`build_gpt_chunk`) — ``q_len = C`` rows
  a slot over the paged cache: a slice of a chunked prefill, or a
  speculative verify step.

Weight sharing: the builders name every parameter explicitly (``gpt_*``),
so the programs resolve to the same scope entries; only the prefill
builder's startup program initializes them (the others discard theirs).
State-var shapes are returned for the serving layer's reset path
(``serving.generate``).
"""
from __future__ import annotations

import dataclasses
import math

from .. import layers
from ..framework import Program, program_guard
from ..initializer import TruncatedNormal
from ..param_attr import ParamAttr
from . import decoder
from .decoder import PREFILL_FEEDS, merge_state, split_heads

__all__ = ["GptConfig", "build_gpt_prefill", "build_gpt_decode",
           "build_gpt_chunk", "build_gpt_generative"]


@dataclasses.dataclass
class GptConfig:
    vocab_size: int = 50257
    hidden_size: int = 768
    num_layers: int = 12
    num_heads: int = 12
    intermediate_size: int = 3072
    max_position: int = 1024
    initializer_range: float = 0.02

    @staticmethod
    def base():
        return GptConfig()

    @staticmethod
    def tiny():
        """CI-sized config (the load_check --decode probe)."""
        return GptConfig(vocab_size=128, hidden_size=64, num_layers=2,
                         num_heads=2, intermediate_size=128,
                         max_position=128)


def _attr(name: str, rng: float):
    return ParamAttr(name=name, initializer=TruncatedNormal(0.0, rng))


def _embed(ids, pos_ids, cfg: GptConfig):
    """Token plus position embeddings: one builder for every phase, so
    they stay bit-identical."""
    tokens = layers.embedding(
        ids, (cfg.vocab_size, cfg.hidden_size),
        param_attr=_attr("gpt_word_emb", cfg.initializer_range))
    return layers.elementwise_add(tokens, layers.embedding(
        pos_ids, (cfg.max_position, cfg.hidden_size),
        param_attr=_attr("gpt_pos_emb", cfg.initializer_range)))


def _ln(x, prefix: str, axis: int = 2):
    return layers.layer_norm(x, begin_norm_axis=axis,
                             param_attr=ParamAttr(name=f"{prefix}_scale"),
                             bias_attr=ParamAttr(name=f"{prefix}_bias"))


def _proj(x, size, name, cfg: GptConfig, act=None):
    return layers.fc(x, size, num_flatten_dims=2, act=act,
                     param_attr=_attr(f"{name}_w", cfg.initializer_range),
                     bias_attr=ParamAttr(name=f"{name}_b"))


def _block(x, i: int, cfg: GptConfig, S: int, attend):
    """One layer on the residual stream ``x`` [B, S, H]. ``attend(i, q, k,
    v)`` is the phase's cache handle: it stores ``k``/``v`` ([B, heads, S,
    D]) in layer ``i``'s cache and returns the attended context."""
    p, H, nh = f"gpt_l{i}", cfg.hidden_size, cfg.num_heads
    h = _ln(x, f"{p}_ln1")
    q, k, v = (split_heads(_proj(h, H, f"{p}_{n}", cfg), S, nh, H // nh)
               for n in "qkv")
    ctx = layers.reshape(layers.transpose(attend(i, q, k, v), [0, 2, 1, 3]),
                         [0, S, H])
    x = layers.elementwise_add(x, _proj(ctx, H, f"{p}_out", cfg))
    h = _proj(_ln(x, f"{p}_ln2"), cfg.intermediate_size, f"{p}_ffn1", cfg,
              act="gelu")
    return layers.elementwise_add(x, _proj(h, H, f"{p}_ffn2", cfg))


def _stack(x, cfg: GptConfig, S: int, attend):
    for i in range(cfg.num_layers):
        x = _block(x, i, cfg, S, attend)
    return _ln(x, "gpt_lnf")


def _logits(h2d, block):
    """[B|BS, H] hidden rows -> vocab logits via the tied word embedding."""
    return layers.matmul(h2d, block.var("gpt_word_emb"), transpose_y=True)


def _state_vars(block, cfg: GptConfig, batch_slots: int, max_seq: int):
    """The generation state (``decoder.state_table``) and one paged K/V
    cache pair per layer, f32. A slot inside a chunked prefill keeps its
    gate closed, as a retired one does."""
    mk, sv, tok, pos, active = decoder.state_table(block, "gpt", batch_slots)
    shape = (batch_slots, cfg.num_heads, max_seq,
             cfg.hidden_size // cfg.num_heads)
    caches = [tuple(mk(f"gpt_kv_{kv}_{i}", shape, "float32") for kv in "kv")
              for i in range(cfg.num_layers)]
    return tok, pos, active, caches, sv


def _scale(cfg: GptConfig) -> float:
    return 1.0 / math.sqrt(cfg.hidden_size // cfg.num_heads)


def build_gpt_prefill(cfg: GptConfig, batch_slots: int, prompt_bucket: int,
                      max_seq: int, page_size: int = 8,
                      strategy: str = "greedy", temperature: float = 1.0,
                      top_k: int = 0, fetch_logits: bool = False,
                      startup: Program = None, rows: int = None):
    """The full-sequence phase for ONE prompt bucket (prompts padded to
    ``prompt_bucket`` tokens). A dispatch carries ``rows`` <= ``batch_slots``
    sequences, each naming the slot it is for, and costs ``rows x
    prompt_bucket`` positions whichever slots they are; the feeds are
    ``decoder.prefill_feeds``'. Where the caller names no ``rows``: a quarter
    of the slots, at least one. A saturated closed loop at 16 decode steps
    a turn seats about an eighth of its slots a turn (7.8 of 64, deviation
    under 3), so a quarter takes a turn's newcomers in one dispatch all but
    one turn in a thousand, at a quarter of what a row for every slot costs
    (PERF.md section 6, PR 40).

    The returned dict carries ``"rows"``, which is how
    ``serving.GenerativeEngine`` groups newcomers and reads
    ``first_token`` ([R, 1]) by row. Pass ``startup`` to share one startup
    program across buckets (only the first call's parameter initializers
    land there)."""
    if prompt_bucket > max_seq:
        raise ValueError(f"prompt_bucket {prompt_bucket} exceeds the KV "
                         f"capacity max_seq {max_seq}")
    decoder.check_pages(max_seq, page_size)
    B, S = batch_slots, prompt_bucket
    R = decoder.prefill_rows(rows, B, max(1, B // 4))
    main = Program()
    own_startup = startup is None
    startup = startup if startup is not None else Program()
    with program_guard(main, startup if own_startup else Program()):
        ids, pos_ids, pmask, plen, smask, slots = decoder.prefill_feeds(R, S)
        tok, pos, active, caches, sv = _state_vars(main.global_block, cfg,
                                                   B, max_seq)
        x = _embed(ids, pos_ids, cfg)
        h = _stack(x, cfg, S, decoder.bulk_attend(caches, pmask, smask, slots,
                                                  _scale(cfg)))
        one = layers.fill_constant([R, 1], "int64", 1)
        last_h = layers.sequence_gather(h, layers.elementwise_sub(plen, one))
        logits = _logits(last_h, main.global_block)         # [R, V]
        first_tok = layers.sample_token(logits, strategy=strategy,
                                        temperature=temperature, top_k=top_k)
        decoder.commit_prefill(tok, pos, active, slots, first_tok, plen,
                               smask)
        out = {"main": main, "startup": startup,
               "first_token": first_tok, "state_vars": sv, "rows": R,
               "feeds": PREFILL_FEEDS}
        if fetch_logits:
            # all-position logits for the continuity tests
            flat = layers.reshape(h, [0, S * cfg.hidden_size])
            flat = layers.reshape(flat, [R * S, cfg.hidden_size])
            out["logits"] = layers.reshape(
                _logits(flat, main.global_block), [R, S, cfg.vocab_size])
            out["last_logits"] = logits
    return out


def build_gpt_decode(cfg: GptConfig, batch_slots: int, max_seq: int,
                     page_size: int = 8, strategy: str = "greedy",
                     temperature: float = 1.0, top_k: int = 0,
                     fetch_logits: bool = False):
    """The per-token phase (module docstring). Fetch ``next_token`` ([B, 1]
    int64; stacked [steps, B, 1] under ``run_chained``). Sequences at
    different positions batch together: the position is data, not shape,
    so every chunk reuses one executable. The active gate keeps retired
    and mid-chunk-prefill slots' caches bit-untouched while their
    neighbours decode."""
    decoder.check_pages(max_seq, page_size)
    B = batch_slots
    main = Program()
    with program_guard(main, Program()):
        tok, pos, active, caches, sv = _state_vars(main.global_block, cfg,
                                                   B, max_seq)
        pos_cap = layers.fill_constant([B, 1], "int64",
                                       cfg.max_position - 1)
        # lookup_table squeezes the trailing ids dim ([B,1] -> [B,H]);
        # restore the length-1 sequence axis the layer stack expects
        x = layers.unsqueeze(
            _embed(tok, layers.elementwise_min(pos, pos_cap), cfg), [1])
        h = _stack(x, cfg, 1, decoder.step_attend(caches, pos, active,
                                                  _scale(cfg), page_size))
        logits = _logits(layers.reshape(h, [0, cfg.hidden_size]),
                         main.global_block)                  # [B, V]
        next_tok = layers.sample_token(logits, strategy=strategy,
                                       temperature=temperature, top_k=top_k)
        decoder.commit_decode(tok, pos, active, next_tok, max_seq)
    out = decoder.decode_net(
        main, caches, sv, {c.name: "full" for pair in caches for c in pair},
        active, next_token=next_tok)
    if fetch_logits:
        out["logits"] = logits
    return out


def build_gpt_chunk(cfg: GptConfig, batch_slots: int, chunk: int,
                    max_seq: int, page_size: int = 8,
                    strategy: str = "greedy", temperature: float = 1.0,
                    top_k: int = 0, mode: str = "prefill"):
    """The q_len=C chunk phase over the paged cache — one program serves
    two schedulers:

    * ``mode='prefill'`` — one C-token slice of a chunked prefill: a long
      cold prompt (or the un-cached suffix after a prefix-cache hit) is
      admitted slice by slice between decode chunks, so resident decoders
      never stall behind a monolithic prefill. Feeds: ``chunk_ids`` [B, C]
      int64 (this slice's tokens, padded); ``chunk_pos`` [B, C] int64
      (absolute position ids, host-fed, clamped to the position table);
      ``chunk_start`` [B, 1] int64 (cache rows already written: the
      slice's append position); ``chunk_len`` [B, 1] int64 (real tokens in
      this slice, 1..C); ``slot_mask`` [B, 1] float32 (slots in this
      dispatch); ``sample_mask`` [B, 1] float32 (1 on a prompt's FINAL
      slice: sample the first generated token from position ``chunk_len -
      1``, commit it to the token state and flip the slot's decode gate).
      Position state advances by ``chunk_len`` on every slice
      (slot-masked); padding rows past ``chunk_len`` write K/V at positions
      the next slice overwrites, and the per-row causal mask keeps them
      out of every real query's softmax.

    * ``mode='verify'`` — the speculative-decoding verify step
      (C = 1 + draft length): ``chunk_ids`` carries the last committed
      token followed by the draft's proposals, the target scores every
      position in ONE dispatch, and ``layers.spec_accept`` commits the
      longest agreeing prefix + bonus token wholly in-program. Extra
      feed ``draft_ids`` [B, C-1] int64; no ``chunk_len``/``sample_mask``
      (a verify chunk is always full). Fetches ``sampled`` [B, C] (the
      target's token at every chunk position — the host streams
      ``sampled[:m+1]``) and ``accept_len`` [B, 1].
    """
    if mode not in ("prefill", "verify"):
        raise ValueError(f"build_gpt_chunk: mode must be 'prefill' or "
                         f"'verify', got {mode!r}")
    if chunk < 1:
        raise ValueError(f"build_gpt_chunk: chunk must be >= 1, got {chunk}")
    if mode == "verify" and chunk < 2:
        raise ValueError("build_gpt_chunk: a verify chunk needs >= 2 "
                         "positions (one committed token + >= 1 draft)")
    decoder.check_pages(max_seq, page_size)
    B, C = batch_slots, chunk
    feeds = {"chunk_ids": ([B, C], "int64"), "chunk_pos": ([B, C], "int64"),
             "chunk_start": ([B, 1], "int64"),
             "slot_mask": ([B, 1], "float32")}
    if mode == "prefill":
        feeds.update(chunk_len=([B, 1], "int64"),
                     sample_mask=([B, 1], "float32"))
        order = ("chunk_ids", "chunk_pos", "chunk_start", "chunk_len",
                 "slot_mask", "sample_mask")
    else:
        feeds["draft_ids"] = ([B, C - 1], "int64")
        order = tuple(feeds)
    sample = dict(strategy=strategy, temperature=temperature, top_k=top_k)
    main = Program()
    with program_guard(main, Program()):
        fed = {n: layers.data(n, shape=shape, dtype=dt,
                              append_batch_size=False)
               for n, (shape, dt) in feeds.items()}
        start, smask = fed["chunk_start"], fed["slot_mask"]
        tok, pos, active, caches, sv = _state_vars(main.global_block, cfg,
                                                   B, max_seq)
        # C-row append + chunk-causal attend in ONE op, at ``chunk_start``
        # under the slot mask
        h = _stack(_embed(fed["chunk_ids"], fed["chunk_pos"], cfg), cfg, C,
                   decoder.step_attend(caches, start, smask, _scale(cfg),
                                       page_size))
        one = layers.fill_constant([B, 1], "int64", 1)
        out = {"main": main, "state_vars": sv, "feeds": order,
               "chunk": C, "mode": mode}
        if mode == "prefill":
            clen = fed["chunk_len"]
            last_h = layers.sequence_gather(
                h, layers.elementwise_sub(clen, one))         # [B, H]
            first_tok = layers.sample_token(
                _logits(last_h, main.global_block), **sample)
            # position advances by the slice length on EVERY slice; the
            # token + decode gate commit only on the final slice
            smask_i64 = layers.cast(smask, "int64")
            inv_s = layers.elementwise_sub(one, smask_i64)
            new_pos = layers.elementwise_add(start, clen)
            layers.assign(merge_state(new_pos, pos, smask_i64, inv_s),
                          output=pos)
            eff = layers.elementwise_mul(smask, fed["sample_mask"])
            eff_i64 = layers.cast(eff, "int64")
            inv_e = layers.elementwise_sub(one, eff_i64)
            layers.assign(merge_state(first_tok, tok, eff_i64, inv_e),
                          output=tok)
            # active := 1 where the slot commits its first token, unchanged
            # elsewhere (the float face of ``merge_state``)
            one_f = layers.fill_constant([B, 1], "float32", 1.0)
            layers.assign(layers.elementwise_add(eff, layers.elementwise_mul(
                active, layers.elementwise_sub(one_f, eff))), output=active)
            out["first_token"] = first_tok
        else:
            flat = layers.reshape(h, [0, C * cfg.hidden_size])
            flat = layers.reshape(flat, [B * C, cfg.hidden_size])
            sampled = layers.sample_token(
                _logits(flat, main.global_block), **sample)   # [B*C, 1]
            sampled_bc = layers.reshape(sampled, [B, C])
            accept, new_tok, new_pos = layers.spec_accept(
                sampled_bc, fed["draft_ids"], start)
            smask_i64 = layers.cast(smask, "int64")
            inv_s = layers.elementwise_sub(one, smask_i64)
            layers.assign(merge_state(new_tok, tok, smask_i64, inv_s),
                          output=tok)
            layers.assign(merge_state(new_pos, pos, smask_i64, inv_s),
                          output=pos)
            out.update(sampled=sampled_bc, accept_len=accept,
                       next_token=new_tok)
    return out


def build_gpt_generative(cfg: GptConfig = None, batch_slots: int = 4,
                         max_seq: int = 64, page_size: int = 8,
                         prompt_buckets=(16,), strategy: str = "greedy",
                         temperature: float = 1.0, top_k: int = 0,
                         fetch_logits: bool = False,
                         prefill_chunk: int = None, spec_k: int = 4,
                         prefill_rows: int = None):
    """Everything the generative serving engine needs
    (``decoder.generative``), with the chunked-prefill and
    speculative-verify chunk programs beside the prefill and decode
    programs, over shared weights.

    ``prefill_rows``: the sequences a bucket prefill dispatch carries
    (``build_gpt_prefill``'s ``rows``; default a quarter of the slots, at
    least one); a turn with more newcomers makes ``ceil(n /
    prefill_rows)`` dispatches. The chunk and verify programs stay
    slot-wide.

    ``prefill_chunk`` (default: one page) sizes the chunked-prefill
    slice; ``spec_k`` sizes the speculative chunk (1 committed token +
    ``spec_k - 1`` drafts per verify dispatch; ``spec_k < 2`` skips
    building the verify program)."""
    cfg = cfg or GptConfig.tiny()
    if cfg.max_position < max_seq:
        raise ValueError(f"max_seq {max_seq} exceeds the position table "
                         f"max_position {cfg.max_position}")
    prompt_buckets = tuple(sorted(set(int(b) for b in prompt_buckets)))
    if not prompt_buckets:
        raise ValueError("need at least one prompt bucket")
    prefill_chunk = int(prefill_chunk or page_size)
    common = dict(page_size=page_size, strategy=strategy,
                  temperature=temperature, top_k=top_k)
    prefill, startup = {}, None
    for S in prompt_buckets:
        prefill[S] = build_gpt_prefill(
            cfg, batch_slots, S, max_seq, fetch_logits=fetch_logits,
            startup=startup, rows=prefill_rows, **common)
        startup = prefill[S]["startup"]
    decode = build_gpt_decode(cfg, batch_slots, max_seq,
                              fetch_logits=fetch_logits, **common)
    net = decoder.generative(cfg, startup, prefill, decode, batch_slots,
                             max_seq, page_size, strategy)
    net.update(
        chunk=build_gpt_chunk(cfg, batch_slots, prefill_chunk, max_seq,
                              mode="prefill", **common),
        verify=build_gpt_chunk(cfg, batch_slots, spec_k, max_seq,
                               mode="verify", **common)
        if spec_k >= 2 else None,
        prefill_chunk=prefill_chunk, spec_k=int(spec_k))
    return net
