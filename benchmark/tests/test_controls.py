"""The controls of ``correct``, kept at a size a test run can hold.

Each limit in a configuration's ``check`` stands between what sound runs of
the program give and what the control gives: the reference put in the
program's place and computed one precision below the configuration's
(fp8 for bf16 operands). Here the control has to come out as not correct
under the committed limits, and the broken-path tests drive the rest of a
run (no look for a chip) with the timed path broken underneath and see
``correct`` come out false."""
import json

import numpy as np
import pytest

import harness
import run as run_mod

BENCH = harness.load_json(harness.REPO, "BENCHMARK.json")


def _cell(name):
    return harness.Cell(BENCH, name, rehearse=True)


def _chips(cell):
    return harness.find_chips(cell)


def test_bert_fp8_control_fails_the_direction_limit_and_bf16_passes():
    from runners import train

    cell = _cell("bert-base.pretrain-s512")
    limit = harness.load_json(
        harness.HERE, "configs",
        "bert-base-pretrain.json")["check"]["limits"]["grad_direction_gap"]
    s = train.Session(cell, _chips(cell))
    s.load(7)
    prog = s.first_steps(3)
    ref = s.follow(3)
    ctl = s.follow(3, precision="fp8")
    sound = train.direction_gap(prog["first_grad"], ref["first_grad"],
                                ref["grad_norm"])
    control = train.direction_gap(ctl["first_grad"], ref["first_grad"],
                                  ref["grad_norm"])
    assert sound <= limit < control, (sound, limit, control)


def test_gpt2_fp8_control_fails_the_logit_gap_limit_at_published_widths():
    """Reference against reference, so no program is needed: the published
    widths and vocabulary, two layers, one 128-token sequence."""
    import jax.numpy as jnp

    from reference import gpt2
    from reference.common import make_weights

    cfg = harness.load_json(harness.HERE, "configs", "gpt2-base-serve.json")
    limit = cfg["check"]["logit_gap_limit"]
    model = dict(cfg["model"], num_layers=2)
    w = make_weights(gpt2.param_spec(model), 11)
    rng = np.random.default_rng(11)
    ids = jnp.asarray(rng.integers(1, model["vocab_size"], 128), jnp.int32)
    best = jnp.argmax(gpt2.logits(w, ids, model), axis=-1).astype(jnp.int32)
    served, ctl = gpt2.gaps_fn(model, "fp8")(w, ids, best)
    assert float(jnp.max(served)) == 0.0       # the reference's own choice
    assert float(jnp.max(ctl)) > limit, float(jnp.max(ctl))


def _result_line(capsys):
    out = capsys.readouterr().out.strip().splitlines()
    return json.loads(out[-1]), out


def test_a_training_step_that_leaves_its_state_unchanged_is_not_correct(
        capsys):
    def broken(session):
        real = session.step

        def step(i):
            loss = real(i)
            session.plant(session.seed)        # the update is thrown away
            return loss

        session.step = step

    rc = run_mod.main(["--workload", "bert-base.pretrain-s512", "--seed",
                       "5", "--seconds", "1", "--trace", "0", "--rehearse"],
                      broken=broken)
    last, out = _result_line(capsys)
    assert last["correct"] is False and rc != 0
    assert any("param_change_norm_gap_worst_leaf" in l and "NOT CORRECT" in l
               for l in out)


def test_a_token_altered_where_it_is_produced_is_not_correct(capsys):
    def broken(session):
        eng = session.eng
        real = eng._emit
        vocab = session.vocab

        def emit(r, toks, *a, **kw):
            toks = [(t + 1) % vocab for t in toks]
            return real(r, toks, *a, **kw)

        eng._emit = emit

    rc = run_mod.main(["--workload", "gpt2-base.decode-saturated", "--seed",
                       "5", "--seconds", "2", "--trace", "0", "--rehearse"],
                      broken=broken)
    last, out = _result_line(capsys)
    assert last["correct"] is False and rc != 0
    assert any("served_logit_gap_max" in l and "NOT CORRECT" in l
               for l in out)
