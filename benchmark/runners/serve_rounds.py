"""The ``serve_rounds`` runner: ``runners.serve_stored`` for a closed loop
whose window sends only a part of its pool. Weights, window, clients, the
comparison with the reference, every check and the free text are that
runner's, by import. One thing differs: the order in which the callers
take the pool's requests.

``traffic.closed_loop`` deals a seed the mix's fixed set of sizes in a
plain permutation, so that "runs differ in what they send when, never in
how much work they offer". That holds where a window goes round the pool
several times. Where it sends some 210 of 256 sizes with a heavy tail
(``decode-mixed-lengths``: prompts lognormal, 64-3,584), WHICH sizes a seed
leaves out is how much work it offers, and the rate follows it.

Here the same requests (the generator's own list for the seed: the same
multiset of sizes under ``mix_seed``, the same tokens) are dealt in
``rounds``. The sizes are put in classes of ``rounds`` neighbours: sorted by
prompt length and cut into bands, a band sorted by answer length and cut
into ``answer_classes`` classes. The seed orders each class; round ``r``
holds the ``r``-th of every class, in the seed's order. Any stretch of one
round's length then offers the same mix of sizes on every seed, and a seed
still decides which of a class's neighbours comes when and the order
inside a round. The mix's file asks for it: ``"deal": {"rounds": n,
"answer_classes": m}``.
"""
from __future__ import annotations

import numpy as np

from harness import say
from runners import serve as base
from runners import serve_stored as stored


def in_rounds(reqs: list, seed: int, rounds: int,
              answer_classes: int = 1) -> list:
    """``reqs`` (a seed's pool as ``traffic.closed_loop`` made it) in
    another order: round after round, each holding one request of every
    class of ``rounds`` neighbours in size."""
    rng = np.random.default_rng([int(seed), 0xDEA1])
    prompts = np.array([len(r.prompt) for r in reqs])
    answers = np.array([r.max_new for r in reqs])
    by_prompt = np.argsort(prompts, kind="stable")
    classes = []
    for lo in range(0, len(reqs), rounds * answer_classes):
        band = by_prompt[lo:lo + rounds * answer_classes]
        band = band[np.argsort(answers[band], kind="stable")]
        classes += [rng.permutation(band[i:i + rounds])
                    for i in range(0, len(band), rounds)]
    order = []
    for r in range(rounds):
        order += list(rng.permutation([c[r] for c in classes if len(c) > r]))
    return [reqs[j] for j in order]


class Session(stored.Session):
    def window(self, mix, seconds, trace) -> dict:
        """``runners.serve.Session.window`` with the closed loop's pool
        dealt in rounds."""
        deal = mix["deal"]
        theirs = base.traffic_mod.closed_loop

        def closed_loop(mix, seed, vocab):
            reqs = in_rounds(theirs(mix, seed, vocab), seed,
                             int(deal["rounds"]),
                             int(deal.get("answer_classes", 1)))
            fill = reqs[:int(mix["clients"])]
            say(f"pool of {len(reqs)} dealt in {deal['rounds']} rounds; the "
                f"first {len(fill)} requests hold "
                f"{sum(len(r.prompt) for r in fill)} prompt tokens and ask "
                f"for {sum(r.max_new for r in fill)}")
            return reqs

        # runners.serve's window asks its traffic module by this name
        base.traffic_mod.closed_loop = closed_loop
        try:
            return super().window(mix, seconds, trace)
        finally:
            base.traffic_mod.closed_loop = theirs


def run(cell, chips, args, t_process, broken=None):
    # runners.serve_stored.run builds its session by the module's name
    theirs, stored.Session = stored.Session, Session
    try:
        return stored.run(cell, chips, args, t_process, broken=broken)
    finally:
        stored.Session = theirs
