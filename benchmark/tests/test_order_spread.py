"""``tools/order_spread.py``: the scheduler's turns of a closed-loop window
played in arithmetic from a seed's deal of the pool. The two cells on
``decode-saturated`` read steady at their committed settings; the tool sees
the fault it is kept for (GPT-2 at ``decode_chunk`` 4, the cell PR 36
re-cut); and the deal is ``traffic.py``'s own."""
import pytest

import harness
import traffic
from tools import order_spread

SEEDS = range(2147483659, 2147483671)
MIX = harness.load_json(harness.HERE, "traffic", "decode-saturated.json")
# a decode step, a prefill dispatch and the host's wait a turn, ms, as read
# on the chip. GPT-2: the walls `tools/turns.py` read at 16 steps a turn (a
# decode dispatch 91.1 ms, a prefill 35.0, the window less both over the
# turns 6.5; my chip runs, PR 36). Command A+: its configuration's `assumed`
# (PR 27) and the ledger's `decode_dispatch_ms.moe` 267.3 for 16 steps
COSTS = {
    "gpt2-base-serve": dict(step_ms=5.70, prefill_ms=35.0, wait_ms=6.5),
    "command-a-plus-ep8-serve": dict(step_ms=16.7, prefill_ms=103.0,
                                     wait_ms=10.0),
}
# GPT-2 at 4 steps a turn, as the cell ran until PR 36: a dispatch 25.9 ms
AT_4 = dict(step_ms=6.47, prefill_ms=35.0, wait_ms=3.3, decode_chunk=4)


def _read(config, **settings):
    return order_spread.read("decode-saturated", config, SEEDS,
                             **dict(COSTS[config], **settings))


@pytest.mark.parametrize("config", sorted(COSTS))
def test_committed_setting_does_not_show_the_order(config):
    """0.022% and 0.073% as the tool reads them; a cell is admitted under
    0.75% between quartiles and the machine's own jitter needs most of
    that. On the chip GPT-2's six seeds read 0.16% (PR 36)."""
    got = _read(config)
    assert got["spread"] < 0.002
    assert all(r["window_s"] >= 40.0 for r in got["rows"])


@pytest.mark.parametrize("config", sorted(COSTS))
def test_every_turn_has_a_refill_at_the_committed_setting(config):
    """Once the first sequences have ended: about 8 of 64 end a turn of 16
    steps, so no turn goes without its prefill dispatch and a turn's cost
    does not hang on the deal."""
    for row in _read(config)["rows"]:
        assert row["dry_turns_after_first_refill"] == 0
        assert 6.0 < row["sequences_a_refill"] < 9.5


def test_the_tool_sees_the_order_at_chunk_4():
    """GPT-2 at 4 steps a turn: two sequences end a turn on average, 40 to
    95 turns of 660 find none, and how many is the deal's: 3.1% between
    quartiles and a median of 4,113 tokens/s here; on the chip six seeds
    read 4.7% and 4,130 where one seed run six times read 1.8% (my chip
    runs, PR 36)."""
    at4 = _read("gpt2-base-serve", **AT_4)
    at16 = _read("gpt2-base-serve")
    assert at4["spread"] >= 2 * at16["spread"]
    assert at4["spread"] > 0.0075
    assert 4000 < at4["median"] < 4250 and 6300 < at16["median"] < 6650
    dry = [r["dry_turns_after_first_refill"] for r in at4["rows"]]
    assert min(dry) > 0 and max(dry) > min(dry)


@pytest.mark.parametrize("seed", [7, 2 ** 31 + 11])
def test_the_deal_is_the_generators_own(seed):
    pool = traffic.closed_loop(MIX, seed, 50257)
    assert order_spread.deal(MIX, seed) == [r.max_new for r in pool]
    assert order_spread.deal(MIX, seed) != order_spread.deal(MIX, seed + 1)
    assert sorted(order_spread.deal(MIX, seed)) == sorted(
        order_spread.deal(MIX, seed + 1))


def test_a_window_by_hand():
    """Two callers on two slots, answers of 3 and 5 tokens, 2 steps a turn;
    a step 1 s, a prefill 10 s, the wait 0.5 s. Turn 0: prefill (2 tokens,
    t 10), decode 2 + 2 (t 12), the first answer is out. Turn 1 (12.5): no
    seat yet (the caller's next request comes a turn late), decode 2 (t
    14.5), the second is out. Turn 2 (15): the first caller's next request
    (3 tokens: the pool goes round) is seated, prefill (t 25), decode 2 (t
    27). Turn 3 (27.5): the second caller's (5 tokens) is seated, prefill
    (t 37.5) and the window of 30 s closes with that burst."""
    got = order_spread.play([3, 5], clients=2, slots=2, chunk=2, step_s=1.0,
                            prefill_s=10.0, wait_s=0.5, seconds=30.0)
    assert got["tokens"] == 2 + 4 + 2 + 1 + 2 + 1
    assert got["window_s"] == pytest.approx(37.5)
    assert got["turns"] == 4 and got["refill_turns"] == 2
    assert got["dry_turns_after_first_refill"] == 0
    assert got["rate"] == pytest.approx(12 / 37.5)


def test_prefill_rows_makes_a_dispatch_for_every_so_many():
    """Four newcomers at two rows a dispatch cost two prefills."""
    one = order_spread.play([9], 4, 4, 4, 1.0, 10.0, 0.0, 1.0)
    two = order_spread.play([9], 4, 4, 4, 1.0, 10.0, 0.0, 1.0,
                            prefill_rows=2)
    assert one["window_s"] == pytest.approx(10.0)
    assert two["window_s"] == pytest.approx(20.0)
