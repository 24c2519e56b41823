"""The ``serve_blocks`` runner: ``runners.serve_stored`` for a model that
generates by diffusion over blocks (``reference.sdar_moe``). Window,
clients, weights, the other checks and the free text are that runner's, by
import. What differs is the comparison with the reference: a served
answer's tokens are no next-token sequence (a block's positions are
revealed a few at a time, the most confident first), so
``runners.serve.reference_gaps`` cannot score them. Here, for the sampled
requests (``runners.serve.check_sample``: a seeded sample of the window's
finished requests, the longest among them) and in each the last block and
two seeded others, the reference rebuilds the block's state before every
denoise forward from the served tokens and the forward each was revealed
at (``ServingFuture.revealed_at()``), computes the block's logits, and two
gaps are read over the positions that forward revealed:

* ``served_logit_gap_max``: the widest gap by which a revealed token's
  reference logit lies below the reference's best at its position;
* ``reveal_confidence_gap_max``: the widest gap by which a revealed
  position's reference log-confidence lies below the ``n_t``-th best
  masked position's (0 where the reference reveals the same positions).
"""
from __future__ import annotations

import time

import numpy as np

import harness
from harness import say
from runners import serve as base
from runners import serve_stored as stored

OTHER_BLOCKS = 2                # checked in a request beside its last


def check_blocks(prompt_len: int, n_tokens: int, L: int, rng) -> list:
    """The blocks of one answer that are checked: its last, and
    ``OTHER_BLOCKS`` seeded others of those that hold answer tokens."""
    first, last = prompt_len // L, (prompt_len + n_tokens - 1) // L
    rest = rng.permutation(np.arange(first, last))[:OTHER_BLOCKS]
    return sorted(int(b) for b in rest) + [last]


def block_gaps(reference, weights, cfg, sample, max_seq, seed,
               control: str = ""):
    """Per sampled request ``(logit gap, confidence gap, the control's
    two)``, each the widest over the request's checked forwards; and how
    many forwards and revealed tokens were checked."""
    import jax
    import jax.numpy as jnp

    L, T, M = cfg["block_length"], cfg["denoising_steps"], \
        cfg["mask_token_id"]
    kv_fn = jax.jit(lambda w, ids, prec: reference.keys_values(
        w, ids, cfg, prec), static_argnums=2)
    fn = reference.block_check_fn(cfg, control)
    rng = np.random.default_rng([int(seed), 0xB10C])
    rows, forwards, tokens = [], 0, 0
    for r in sample:
        Pn, toks = len(r.prompt), np.asarray(r.tokens, np.int64)
        at = np.asarray(r.fut.revealed_at()[:len(toks)], np.int64)
        ids = np.full(max_seq, M, np.int32)
        ids[:Pn + len(toks)] = np.concatenate([r.prompt, toks])
        kvs = kv_fn(weights, jnp.asarray(ids), "f32")
        low = kv_fn(weights, jnp.asarray(ids), control) if control else kvs
        worst = np.full(4, -np.inf)
        for b in check_blocks(Pn, len(toks), L, rng):
            final = ids[b * L:(b + 1) * L]
            for t, state, revealed in reference.block_states(
                    ids[:Pn + len(toks)], Pn, at, b, L, M):
                if not revealed.any():
                    continue
                n = min(reference.n_transfer(t, L, T), int((state == M).sum()))
                got = fn(weights, kvs, low, jnp.int32(b * L),
                         jnp.asarray(state, jnp.int32), jnp.asarray(final),
                         jnp.asarray(revealed), jnp.int32(n))
                worst = np.maximum(worst, [float(g) for g in got])
                forwards += 1
                tokens += int(revealed.sum())
        rows.append(worst)
    return rows, forwards, tokens


class Session(stored.Session):
    def gaps(self, finished, control=""):
        """``runners.serve.Session.gaps`` by blocks: the logit gaps per
        request (served, control's); the confidence gaps are kept on the
        session (``confidence_gaps``, ``control_confidence_gaps``)."""
        sample = base.check_sample(finished, self.seed,
                                   int(self.cfg["check"]["sample"]))
        t0 = time.perf_counter()
        rows, forwards, tokens = block_gaps(
            self.reference, self.weights, self.model_cfg, sample,
            self.cfg["serving"]["max_seq"], self.seed, control)
        say(f"reference: {len(sample)} requests, {forwards} denoise "
            f"forwards of their blocks, {tokens} revealed tokens, "
            f"{time.perf_counter() - t0:.1f} s (not in setup_s)")
        cols = list(zip(*rows)) if rows else [[], [], [], []]
        self.confidence_gaps = [float(v) for v in cols[1]]
        self.control_confidence_gaps = [float(v) for v in cols[3]]
        return ([float(v) for v in cols[0]], [float(v) for v in cols[2]])


def run(cell, chips, args, t_process, broken=None):
    seen = {}

    def ready(session):
        seen["session"] = session
        if broken:
            broken(session)

    # runners.serve_stored.run builds its session by the module's name
    theirs, stored.Session = stored.Session, Session
    try:
        result = stored.run(cell, chips, args, t_process, broken=ready)
    finally:
        stored.Session = theirs
    conf = seen["session"].confidence_gaps
    limit = cell.config["check"]["confidence_gap_limit"]
    result["checks"].insert(1, {
        "name": "reveal_confidence_gap_max", "value": max(conf, default=None),
        "limit": limit, "rule": "<=",
        "ok": bool(conf) and max(conf) <= limit})
    return result
