"""Will the order in which a seed deals a closed loop's pool show in the
cell's rate? Arithmetic only, no JAX and no chip: before chip time is spent
on a serving cell's ``slots`` / ``decode_chunk`` / ``prefill_rows``.

The pool is dealt for each seed by ``traffic.closed_loop`` itself, and the
scheduler's turns of one window are played from three costs given on the
command line (read them off a run of the cell: ``tools/turns.py``):

    python3 benchmark/tools/order_spread.py --traffic decode-saturated \
        --config gpt2-base-serve --step-ms 5.70 --prefill-ms 35.0 \
        --wait-ms 6.5 [--decode-chunk 4] [--slots 64] [--prefill-rows 16] \
        [--seeds 12] [--seconds 40]

A turn, as ``serving/generate.py`` makes it: seat the queued requests in
the free slots; if any were seated, one prefill dispatch (or one for every
``prefill_rows`` of them) that gives each its first token; one chained
decode dispatch of ``decode_chunk`` steps for every seated sequence; the
host's wait. A sequence that ends inside a chunk leaves its slot empty for
the rest of it, and its caller's next request is seated ``--lag-turns``
whole turns later (1: the caller runs while the next dispatch is in
flight, after that turn's seats were given). The window closes with the
first burst of tokens at or after ``--seconds``, as ``runners/serve.py``
closes it, and the rate is every token before that over that time.

Prints a line a seed (tokens, turns, turns with a refill, rate), then the
spread between quartiles over the median. Against the chip (GPT-2, PR 36,
each chunk's own walls): 4,113 / 5,456 / 6,491 tokens/s at 4 / 8 / 16 where
the cell read 4,130 / 5,436 / 6,468; at 32 it reads 6,566 for 7,659,
because a settle that hands back 15 answers is long enough for their
callers to be seated at once (lag between 0 and 1). What it cannot see: the
machine's jitter, and costs that move with the sequences a dispatch carries.
"""
import argparse
import json
import math
import os
import statistics
import sys

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, HERE)

import harness                                              # noqa: E402
import traffic                                              # noqa: E402


def deal(mix: dict, seed: int) -> list:
    """A seed's pool as the runner's clients take it: ``max_new`` of each
    request in turn (a prompt's length does not enter a bucket's cost)."""
    return [r.max_new for r in traffic.closed_loop(mix, seed, vocab=1 << 15)]


def play(answers, clients: int, slots: int, chunk: int, step_s: float,
         prefill_s: float, wait_s: float, seconds: float,
         prefill_rows=None, lag_turns: int = 1) -> dict:
    left = [0] * slots                 # decode tokens a slot's sequence owes
    queue = list(range(min(clients, slots)))
    later = {}                         # turn -> requests that arrive then
    sent = len(queue)
    t = tokens = 0
    seats = []                         # sequences seated, turn by turn

    def closed():
        return t >= seconds

    while True:
        queue += later.pop(len(seats), [])
        seated = []
        for j in range(slots):
            if not queue:
                break
            if left[j] == 0:
                left[j] = answers[queue.pop(0) % len(answers)]
                seated.append(j)
        seats.append(len(seated))
        if seated:
            t += prefill_s * (math.ceil(len(seated) / prefill_rows)
                              if prefill_rows else 1)
            tokens += len(seated)
            ended = [j for j in seated if left[j] == 1]
            for j in seated:
                left[j] -= 1
            if closed():
                break
        else:
            ended = []
        if any(left):
            t += chunk * step_s
            for j in range(slots):
                take = min(chunk, left[j])
                if take:
                    tokens += take
                    left[j] -= take
                    if left[j] == 0:
                        ended.append(j)
            if closed():
                break
        elif not seated and not later and not queue:
            raise ValueError("order_spread: nothing left to serve")
        for _ in ended:                # each caller sends its next request
            later.setdefault(len(seats) + lag_turns, []).append(sent)
            sent += 1
        t += wait_s
    refills = [k for k, n in enumerate(seats) if n and k]
    return {"tokens": tokens, "turns": len(seats), "window_s": t,
            "refill_turns": len(refills),
            "dry_turns_after_first_refill":
                len(seats) - refills[0] - len(refills) if refills else None,
            "sequences_a_refill":
                sum(seats[1:]) / len(refills) if refills else None,
            "rate": tokens / t}


def spread(values) -> float:
    q = statistics.quantiles(values, n=4)
    return (q[2] - q[0]) / statistics.median(values)


def trimmed_spread(values) -> float:
    """The same with the run farthest from the median left out, where that
    narrows it: how the check takes a set's spread."""
    med = statistics.median(values)
    rest = sorted(values, key=lambda v: abs(v - med))[:-1]
    return min(spread(values), spread(rest)) if len(rest) >= 2 \
        else spread(values)


def read(traffic_name: str, config_name: str, seeds, *, step_ms: float,
         prefill_ms: float, wait_ms: float, seconds: float = 40.0,
         decode_chunk=None, slots=None, prefill_rows=None,
         lag_turns: int = 1) -> dict:
    """One row a seed and their spread, for the committed files with any
    of the three settings replaced."""
    mix = harness.load_json(HERE, "traffic", traffic_name + ".json")
    serving = harness.load_json(HERE, "configs",
                                config_name + ".json")["serving"]
    rows = [dict(seed=seed, **play(
        deal(mix, seed), int(mix["clients"]),
        int(slots or serving["slots"]),
        int(decode_chunk or serving["generation"]["decode_chunk"]),
        step_ms / 1e3, prefill_ms / 1e3, wait_ms / 1e3, seconds,
        prefill_rows or serving.get("prefill_rows"), lag_turns))
        for seed in seeds]
    rates = [r["rate"] for r in rows]
    return {"rows": rows, "median": statistics.median(rates),
            "spread": spread(rates) if len(rates) > 1 else None}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--traffic", required=True)
    ap.add_argument("--config", required=True)
    ap.add_argument("--step-ms", type=float, required=True)
    ap.add_argument("--prefill-ms", type=float, required=True)
    ap.add_argument("--wait-ms", type=float, required=True)
    ap.add_argument("--decode-chunk", type=int)
    ap.add_argument("--slots", type=int)
    ap.add_argument("--prefill-rows", type=int)
    ap.add_argument("--lag-turns", type=int, default=1)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--first-seed", type=int, default=2147483659)
    ap.add_argument("--seconds", type=float, default=40.0)
    a = ap.parse_args()
    got = read(a.traffic, a.config,
               range(a.first_seed, a.first_seed + a.seeds),
               step_ms=a.step_ms, prefill_ms=a.prefill_ms,
               wait_ms=a.wait_ms, seconds=a.seconds,
               decode_chunk=a.decode_chunk, slots=a.slots,
               prefill_rows=a.prefill_rows, lag_turns=a.lag_turns)
    for row in got["rows"]:
        print(json.dumps(row))
    print(json.dumps({"median": got["median"], "spread": got["spread"]}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
