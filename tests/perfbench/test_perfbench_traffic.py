"""The traffic generator is a pure function of (workload file, seed)."""
import json
import os

import numpy as np
import pytest

from perfbench.harness import cell, traffic

SERVE = ["gpt2-serve-decode", "gpt2-serve-short"]


def _workload(root, name):
    with open(os.path.join(root, "perfbench", "workloads",
                           name + ".json")) as f:
        return json.load(f)


@pytest.fixture
def OPEN(root):
    return _workload(root, "gpt2-serve-short")


@pytest.fixture
def CLOSED(root):
    return _workload(root, "gpt2-serve-decode")


def test_open_schedule_is_a_pure_function_of_the_seed(OPEN):
    a = traffic.open_schedule(OPEN, 7, 5.0)
    b = traffic.open_schedule(OPEN, 7, 5.0)
    c = traffic.open_schedule(OPEN, 8, 5.0)
    assert a == b and a != c
    ids = [traffic.prompt_ids(r, 50257) for r in a[:5]]
    again = [traffic.prompt_ids(r, 50257) for r in b[:5]]
    assert all(np.array_equal(x, y) for x, y in zip(ids, again))


def test_open_schedule_rate_lead_and_order(OPEN):
    reqs = traffic.open_schedule(OPEN, 3, 30.0)
    due = [r.due_s for r in reqs]
    assert due == sorted(due) and due[0] >= -OPEN["lead_s"] and due[-1] < 30
    rate = OPEN["arrivals"]["rate_per_s"]
    assert len(reqs) == round(rate * OPEN["lead_s"]) + rate * 30


def test_every_seed_offers_the_same_work(OPEN):
    """Steady by a fixed amount of work drawn from the seed: the same
    count, the same prompt and reply tokens in the window and in every
    block; only order, pairing and timing differ."""
    def work(seed, t0, t1):
        reqs = [r for r in traffic.open_schedule(OPEN, seed, 33.0)
                if t0 <= r.due_s < t1]
        return (len(reqs), sum(r.prompt_len for r in reqs),
                sum(r.n_new for r in reqs))

    assert work(1, 0, 30) == work(2, 0, 30) == work(99, 0, 30)
    assert work(1, 5, 10) == work(2, 20, 25)
    assert work(1, 0, 30)[0] == 30 * OPEN["arrivals"]["rate_per_s"]
    a = traffic.open_schedule(OPEN, 1, 30.0)
    b = traffic.open_schedule(OPEN, 2, 30.0)
    assert [r.n_new for r in a] != [r.n_new for r in b]
    # arrivals inside a block are independent uniform times: the gaps
    # are as irregular as a Poisson process's (cv of exponential = 1)
    gaps = np.diff([r.due_s for r in a])
    assert 0.85 < np.std(gaps) / np.mean(gaps) < 1.15


@pytest.mark.parametrize("name", SERVE)
def test_lengths_respect_the_clips_and_the_median(root, name):
    w = _workload(root, name)
    rng = np.random.default_rng(0)
    for key in ("prompt_len", "output_len"):
        xs = [traffic.draw_length(w[key], rng) for _ in range(4000)]
        assert min(xs) >= w[key]["min"] and max(xs) <= w[key]["max"]
        assert np.median(xs) == pytest.approx(w[key]["median"], rel=0.1)


@pytest.mark.parametrize("name", SERVE)
def test_traffic_fits_the_configuration(root, name):
    """Choose traffic on which no operation fails: the longest prompt
    plus the longest reply fit the model and the engine's ceiling."""
    c, config, w = cell.load_cell(name, root)
    _, longest, new = traffic.limits(w)
    assert longest + new <= config["n_positions"]
    assert new <= config["serve"]["engine"]["max_new_tokens"]


def test_closed_requests_and_the_steady_state_start(CLOSED):
    a = traffic.closed_request(CLOSED, 5, 3, 2)
    assert a == traffic.closed_request(CLOSED, 5, 3, 2)
    assert a != traffic.closed_request(CLOSED, 5, 4, 2)
    firsts = [traffic.closed_request(CLOSED, 5, c, 0).n_new
              for c in range(64)]
    # first replies are cut by a uniform draw: many under the clip's floor
    assert min(firsts) < CLOSED["output_len"]["min"] and min(firsts) >= 1
    later = [traffic.closed_request(CLOSED, 5, c, 1).n_new
             for c in range(64)]
    assert min(later) >= CLOSED["output_len"]["min"]


def test_prompt_buckets_cover_the_rungs_the_traffic_reaches(OPEN, CLOSED):
    assert traffic.prompt_buckets(CLOSED) == [32, 64, 128, 256]
    assert traffic.prompt_buckets(OPEN) == [8, 16, 32, 64, 128]
    assert traffic.limits(CLOSED) == (32, 256, 512)


def test_bursts_keep_the_mean_rate_and_classes_mix(OPEN):
    w = dict(OPEN, arrivals={"rate_per_s": 50, "bursts": {
        "period_s": 4.0, "on_s": 1.0, "factor": 3.0}}, lead_s=0.0)
    reqs = traffic.open_schedule(w, 1, 80.0)
    assert len(reqs) == 50 * 80
    on = sum(1 for r in reqs if (r.due_s % 4.0) < 1.0)
    assert on / len(reqs) == pytest.approx(0.75, abs=0.03)
    mixed = {"loop": "open", "arrivals": {"rate_per_s": 50}, "classes": [
        {"weight": 0.8, "prompt_len": {"dist": "fixed", "value": 8},
         "output_len": {"dist": "fixed", "value": 4}},
        {"weight": 0.2, "prompt_len": {"dist": "fixed", "value": 64},
         "output_len": {"dist": "fixed", "value": 32},
         "temperature": 0.7, "top_k": 40}]}
    reqs = traffic.open_schedule(mixed, 1, 40.0)
    share = sum(1 for r in reqs if r.prompt_len == 64) / len(reqs)
    assert share == pytest.approx(0.2, abs=0.001)
    assert traffic.samplers(mixed) == [(0.7, 40)]
    assert {r.temperature for r in reqs} == {0.0, 0.7}


def test_training_pool_is_seeded():
    w = {"batch": 4, "pool": 2, "inputs": [
        {"shape": [3, 8, 8], "dtype": "float32", "dist": "normal"},
        {"shape": [], "dtype": "int32", "dist": "randint", "high": 10}]}
    a, b = traffic.batches(w, 1), traffic.batches(w, 1)
    assert len(a) == 2 and a[0][0].shape == (4, 3, 8, 8)
    assert a[0][1].dtype == np.int32 and a[0][1].max() < 10
    assert all(np.array_equal(x, y) for p, q in zip(a, b)
               for x, y in zip(p, q))
    assert not np.array_equal(a[0][0], traffic.batches(w, 2)[0][0])
    assert not np.array_equal(a[0][0], a[1][0])
