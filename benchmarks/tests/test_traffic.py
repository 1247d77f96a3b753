"""The general generator: the same work for every seed, another order."""

import glob
import json
import os

import pytest

import traffic
from conftest import BENCH

MIXES = sorted(glob.glob(os.path.join(BENCH, "traffic", "*.json"))
               + glob.glob(os.path.join(BENCH, "rehearsal", "tiny-*[0-9y].json")))


def load(path):
    with open(path) as f:
        return json.load(f)


@pytest.mark.parametrize("path", MIXES, ids=os.path.basename)
def test_mix_file_is_well_formed(path):
    mix = load(path)
    if "lengths" not in mix:
        pytest.skip("not a mix")
    assert mix["loop"] in ("open", "closed")
    assert mix["sharing"] == "none"
    assert ("rate_rps" in mix) == (mix["loop"] == "open")
    assert ("clients" in mix) == (mix["loop"] == "closed")
    assert len(mix["lengths"]) in (4, 16)
    assert int(mix["system_tokens"]) <= 20


def test_open_loop_same_count_and_work_for_every_seed():
    mix = load(os.path.join(BENCH, "traffic", "chat-open-bursty.json"))
    mix.pop("order_seed")
    runs = [traffic.requests(mix, seed, 45.0) for seed in (1, 2, 3)]
    n = round(mix["rate_rps"] * 45.0)
    for reqs in runs:
        assert len(reqs) == n
        assert all(0.0 <= r.due_s < 45.0 for r in reqs)
        assert [r.due_s for r in reqs] == sorted(r.due_s for r in reqs)
    work = [sorted((r.prompt_tokens, r.max_tokens) for r in reqs)
            for reqs in runs]
    assert work[0] == work[1] == work[2]
    assert [r.due_s for r in runs[0]] != [r.due_s for r in runs[1]]


def test_bursts_are_bursts():
    mix = load(os.path.join(BENCH, "traffic", "chat-open-bursty.json"))
    due = traffic.arrivals(mix, 5, 45.0)
    size, within = mix["burst"]["size"], mix["burst"]["within_s"]
    tight = sum(1 for i in range(len(due) - size + 1)
                if due[i + size - 1] - due[i] <= within + 1e-9)
    assert tight >= int(len(due) * mix["burst"]["share"]) // size


def test_order_seed_fixes_the_schedule_and_leaves_the_text_to_the_seed():
    mix = load(os.path.join(BENCH, "traffic", "chat-open-bursty.json"))
    assert "order_seed" in mix
    a, b = (traffic.requests(mix, seed, 45.0) for seed in (1, 2))
    assert [(r.due_s, r.prompt_tokens, r.max_tokens) for r in a] == \
        [(r.due_s, r.prompt_tokens, r.max_tokens) for r in b]
    assert [r.seed for r in a] != [r.seed for r in b]
    assert traffic.body(mix, a[0], 20) != traffic.body(mix, b[0], 20)
    again = traffic.requests(mix, 1, 45.0)
    assert [traffic.body(mix, r, 20) for r in a] == \
        [traffic.body(mix, r, 20) for r in again]


def test_closed_loop_cycles_the_table_in_seeded_order():
    mix = load(os.path.join(BENCH, "traffic", "chat-closed-8.json"))
    mix.pop("order_seed")
    a = traffic.requests(mix, 1, 45.0)
    first = [next(a) for _ in range(32)]
    pairs = [(r.prompt_tokens, r.max_tokens) for r in first]
    assert sorted(pairs[:16]) == sorted(map(tuple, mix["lengths"]))
    assert pairs[:16] == pairs[16:]
    b = traffic.requests(mix, 2, 45.0)
    assert [(r.prompt_tokens, r.max_tokens) for r in
            (next(b) for _ in range(16))] != pairs[:16]


def test_every_pair_stays_64_tokens_short_of_the_context():
    for cfg_path in glob.glob(os.path.join(BENCH, "configs", "*.json")):
        n_ctx = load(cfg_path)["serve"]["n_ctx"]
        for path in glob.glob(os.path.join(BENCH, "traffic", "*.json")):
            for p, o in load(path)["lengths"]:
                assert p + o <= n_ctx - 64, (cfg_path, path, p, o)


def test_prompts_share_nothing_but_the_system_line():
    mix = load(os.path.join(BENCH, "traffic", "chat-closed-1.json"))
    reqs = traffic.requests(mix, 1, 45.0)
    first = next(reqs)
    a, b = traffic.body(mix, first, 20), traffic.body(mix, next(reqs), 20)
    assert a["messages"][0] == b["messages"][0]
    assert a["messages"][1]["content"].split()[:4] != \
        b["messages"][1]["content"].split()[:4]
    assert "seed" not in a and a["stream"] is True
    n = len(a["messages"][1]["content"].split())
    assert n == first.prompt_tokens - 20 - mix["system_tokens"]
    assert a["max_tokens"] == first.max_tokens
