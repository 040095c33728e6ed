"""``correct`` has to be able to fail: the control (the reference in the
next precision down, put in the program's place) comes out not correct,
and so does a run whose timed path is broken underneath."""
import numpy as np
import pytest

from benchmark.tests.conftest import last_json


def _run(cell, capsys, seconds="1.5"):
    from benchmark import run
    assert run.main(["--workload", cell, "--seed", "77", "--seconds",
                     seconds, "--trace", "0"]) == 0
    out, err = capsys.readouterr()
    return last_json(out), err


def _failed(line):
    return sorted(k for k, v in line["checks"].items()
                  if not v["value"] <= v["limit"])


@pytest.mark.parametrize("cell", ["resnet50.train-b256",
                                  "opt-1.3b.train-2k"])
def test_training_control_is_not_correct(toy_harness, cell):
    """The fp8 reference against the float32 one fails a limit that the
    program itself (``test_toy_cell_runs_end_to_end``) passes."""
    from benchmark.drivers import train as D
    c = toy_harness.load_cell(cell)
    fam, cfg, traffic = c["family"], c["cfg"], c["traffic"]
    ref = D.reference_first_steps(fam, cfg, traffic, 77)
    ctl = D.reference_first_steps(fam, cfg, traffic, 77, precision="fp8")
    nums = {k: v[0] for k, v in D.compare(ctl, ref).items()}
    limits = c["limits"]["limits"]
    assert any(nums[k] > limits[k] for k in limits), nums


def test_serving_control_is_not_correct(toy_harness):
    """The token the fp8 reference puts first, judged by the float32
    reference over the same prompts, lies further below the best than the
    limit allows."""
    from benchmark.drivers import serve as D
    c = toy_harness.load_cell("opt-1.3b.serve-chat")
    fam, cfg = c["family"], c["cfg"]
    rng = np.random.default_rng(3)
    seqs = rng.integers(0, cfg["vocab_size"], (4, 48)).astype(np.int32)
    ref = D.reference_logits(fam, cfg, 77, seqs)
    low = D.reference_logits(fam, cfg, 77, seqs, precision="fp8")
    judged = np.ones(seqs.shape, bool)
    assert D.logit_gap(ref, np.asarray(ref).argmax(-1), judged) == 0.0
    gap = D.logit_gap(ref, np.asarray(low).argmax(-1), judged)
    assert gap > c["limits"]["limits"]["logit_gap"]


def test_gap_numbers_by_hand():
    """``logit_gaps`` over the judged positions only: the widest gap, the
    mean and the 99th percentile (an observed value), in standard
    deviations of each row; ``altered_token_gaps`` is the same three with
    ONE token replaced by the next id up."""
    from benchmark.drivers import serve as D
    row = [0.0, 1.0, 2.0]                      # std sqrt(2/3)
    logits = np.array([[row, row, row, [0.0, 0.0, 9.0]]], np.float32)
    tokens = np.array([[2, 1, 0, 0]])          # gaps 0, 1, 2 and unjudged
    judged = np.array([[True, True, True, False]])
    got = D.logit_gaps(logits, tokens, judged)
    sd = float(np.std(row))
    assert got["logit_gap"] == pytest.approx(2.0 / sd, rel=1e-6)
    assert got["logit_gap_mean"] == pytest.approx(1.0 / sd, rel=1e-6)
    assert got["logit_gap_p99"] == got["logit_gap"]
    assert D.logit_gap(logits, tokens, judged) == got["logit_gap"]
    assert set(got) == set(D.GAP_NUMBERS)
    none = D.logit_gaps(logits, tokens, np.zeros_like(judged))
    assert none == dict.fromkeys(D.GAP_NUMBERS, 0.0)
    # every token the best but for the one that is altered: the next id
    # up of 2 is 0 (two below the best), of the others one or none below
    best = np.array([[2, 2, 2, 2]])
    alt = D.altered_token_gaps(logits, best, judged, 3)
    assert alt["altered_least"] == alt["altered_median"] \
        == alt["logit_gap"] == pytest.approx(2.0 / sd, rel=1e-6)
    assert alt["logit_gap_mean"] == pytest.approx(2.0 / sd / 3, rel=1e-6)


SERVE_CELLS = ["opt-1.3b.serve-chat", "zaya1-8b.serve-reason"]


@pytest.mark.parametrize("cell", SERVE_CELLS)
def test_every_serving_cell_is_held_to_the_widest_gap(cell):
    """The cell's own limits, not the toy's: the widest gap is among them
    (the driver refuses a limits file without it), any other is one of
    the driver's numbers, and the toy block holds the toy cell to the
    same names, so what decides ``correct`` in the tests is what decides
    it on the chip."""
    from benchmark import harness as H
    from benchmark.drivers import serve as D
    real = H.load_json(H.CODE, "limits", cell + ".json")
    assert "logit_gap" in real["limits"]
    assert set(real["limits"]) <= set(D.GAP_NUMBERS)
    assert set(real["toy"]["limits"]) == set(real["limits"])


@pytest.mark.parametrize("cell", SERVE_CELLS)
def test_one_wrong_token_among_thousands_fails_the_cells_own_limits(cell):
    """Three thousand judged tokens, each the reference's best but one
    that lies 2.4 standard deviations under it (on the chip one altered
    token reads 2.2-3.5 at the 1st percentile of positions and 4-6 at
    the median: limits/<cell>.json, ``altered``). By the cell's own
    limits, not the toy's, the run is not correct, and the widest gap is
    the only number that says so: the mean moves by 0.0008."""
    from benchmark import harness as H
    from benchmark.drivers import serve as D
    limits = H.load_json(H.CODE, "limits", cell + ".json")["limits"]
    logits = np.tile(np.array([0.0, 1.0, 2.0], np.float32), (3, 1000, 1))
    tokens = np.full((3, 1000), 2)
    judged = np.ones((3, 1000), bool)
    got = D.logit_gaps(logits, tokens, judged)
    assert all(got[k] <= limits[k] for k in limits)
    tokens[1, 517] = 0
    got = D.logit_gaps(logits, tokens, judged)
    assert [k for k in limits if got[k] > limits[k]] == ["logit_gap"]


TRAIN_CELLS = ["resnet50.train-b256", "opt-1.3b.train-2k"]


@pytest.mark.parametrize("cell", TRAIN_CELLS)
@pytest.mark.parametrize("fault", ["lr_x1.3", "half_batch"])
def test_fault_planted_in_the_reference_is_not_correct(toy_harness, cell,
                                                       fault):
    """A wrong update (the learning rate 30% up) and half of the batch
    left out, planted in the reference put in the program's place, each
    fail one of the cell's limits."""
    from benchmark.drivers import train as D
    c = toy_harness.load_cell(cell)
    fam, cfg, traffic = c["family"], c["cfg"], c["traffic"]
    ref = D.reference_first_steps(fam, cfg, traffic, 77)
    bad = D.reference_first_steps(fam, cfg, traffic, 77, fault=fault)
    nums = {k: v[0] for k, v in D.compare(bad, ref).items()}
    limits = c["limits"]["limits"]
    assert any(nums[k] > limits[k] for k in limits), nums


@pytest.mark.parametrize("cell", TRAIN_CELLS)
def test_step_that_leaves_its_state_unchanged(toy_harness, capsys,
                                              monkeypatch, cell):
    from mxnet_tpu.parallel import trainer as tr
    real = tr.make_functional

    def frozen(optimizer):
        init, update = real(optimizer)
        return init, lambda w, g, s, lr, t, rng: (w, s)
    monkeypatch.setattr(tr, "make_functional", frozen)
    line, err = _run(cell, capsys)
    assert line["correct"] is False
    assert {"grad_gap", "delta_gap"} <= set(_failed(line)), err


@pytest.mark.parametrize("cell", TRAIN_CELLS)
def test_wrong_update_in_the_program(toy_harness, capsys, monkeypatch,
                                     cell):
    """The program's optimizer given a learning rate 30% up: the median
    leaf's change departs from the reference's by as much."""
    from mxnet_tpu.parallel import trainer as tr
    real = tr.make_functional

    def hot(optimizer):
        init, update = real(optimizer)
        return init, lambda w, g, s, lr, t, rng: update(w, g, s, 1.3 * lr,
                                                        t, rng)
    monkeypatch.setattr(tr, "make_functional", hot)
    line, err = _run(cell, capsys)
    assert line["correct"] is False
    assert "delta_gap_median" in _failed(line), err


@pytest.mark.parametrize("cell", TRAIN_CELLS)
def test_half_of_the_batch_left_out(toy_harness, capsys, monkeypatch, cell):
    """The second half of every batch replaced by the first: the mean is
    taken over half the rows."""
    import jax.numpy as jnp
    from mxnet_tpu.parallel.trainer import ParallelTrainer
    real = ParallelTrainer._shard_batch

    def halved(self, batch, what):
        out = {}
        for k, v in batch.items():
            v = jnp.asarray(getattr(v, "_val", v))   # fit hands NDArrays
            h = v.shape[0] // 2
            out[k] = jnp.concatenate([v[:h], v[:h]], axis=0)
        return real(self, out, what)
    monkeypatch.setattr(ParallelTrainer, "_shard_batch", halved)
    line, err = _run(cell, capsys)
    assert line["correct"] is False
    assert "grad_gap_median" in _failed(line), err


def test_token_altered_where_it_is_produced(toy_harness, capsys,
                                            monkeypatch):
    from mxnet_tpu.serving.engine import InferenceEngine
    real = InferenceEngine._push_token
    seen = {"n": 0}

    def altered(self, req, slot, t, now):
        seen["n"] += 1
        if seen["n"] % 5 == 0:
            t = (int(t) + 1) % 320
        return real(self, req, slot, t, now)
    monkeypatch.setattr(InferenceEngine, "_push_token", altered)
    line, err = _run("opt-1.3b.serve-chat", capsys, seconds="3")
    assert line["correct"] is False
    assert "logit_gap" in _failed(line), err


@pytest.mark.parametrize("cell", SERVE_CELLS)
def test_one_token_altered_where_it_is_produced(toy_harness, capsys,
                                                monkeypatch, cell):
    """ONE token of the whole run, in the middle of the window's longest
    request (the sample always holds it), replaced by the next id up as
    the engine hands it out."""
    from benchmark import generate as G
    from mxnet_tpu.serving.engine import InferenceEngine
    c = toy_harness.load_cell(cell)
    vocab = c["cfg"]["vocab_size"]
    reqs = G.requests(c["traffic"], vocab, 77, 3.0)
    _, prompt, n = max(reqs, key=lambda r: len(r[1]) + r[2])
    real = InferenceEngine._push_token
    seen = {"n": 0}

    def altered(self, req, slot, t, now):
        if len(req.tokens) == n // 2 and req.limit == n \
                and np.array_equal(req.prompt, prompt) and not seen["n"]:
            seen["n"] += 1
            t = (int(t) + 1) % vocab
        return real(self, req, slot, t, now)
    monkeypatch.setattr(InferenceEngine, "_push_token", altered)
    line, err = _run(cell, capsys, seconds="3")
    assert seen["n"] == 1
    assert line["correct"] is False
    assert "logit_gap" in _failed(line), err
