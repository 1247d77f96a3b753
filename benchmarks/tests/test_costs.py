"""Bytes of a decode step and FLOPs of a prefill, against hand arithmetic."""

import json
import os

import pytest

import costs
from conftest import BENCH


def cfg(name):
    with open(os.path.join(BENCH, "configs", name + ".json")) as f:
        return json.load(f)


def test_mistral_decode_step_bytes():
    c = cfg("mistral-7b-v0.2-q4km-8lane")
    q4 = (4096 * 4096 * 2 + 1024 * 4096 + 14336 * 4096 * 2) * 144 // 256
    q6 = (1024 * 4096 + 14336 * 4096) * 210 // 256
    norms = 2 * 4096 * 4
    per_layer = q4 + q6 + norms
    head = 32000 * 4096 * 210 // 256 + 4096 * 4
    assert costs.weight_bytes_per_step(c) == 32 * per_layer + head
    assert costs.kv_bytes_per_token(c) == 2 * 32 * 1024 * 2
    got = costs.decode_step_bytes(c, lanes=8, context_tokens=500)
    assert got == 32 * per_layer + head + 8 * 500 * 131072 + 8 * 4096 * 2


def test_solar_is_48_layers_of_the_same_block():
    m, s = cfg("mistral-7b-v0.2-q4km-8lane"), cfg("solar-10.7b-v1-q4km-serial")
    head = 32000 * 4096 * 210 // 256 + 4096 * 4
    per_layer = (costs.weight_bytes_per_step(m) - head) // 32
    assert costs.weight_bytes_per_step(s) == 48 * per_layer + head


def test_prefill_flops():
    c = cfg("mistral-7b-v0.2-q4km-8lane")
    layer = 4096 * 4096 * 2 + 1024 * 4096 * 2 + 14336 * 4096 * 3
    n = 1024
    want = 2.0 * 32 * layer * n + 2.0 * 32000 * 4096 \
        + 2.0 * 4096 * n * n * 32
    assert costs.prefill_flops(c, n) == pytest.approx(want)


def test_roofline_names_its_bound():
    peak = costs.peaks("TPU v5 lite")
    assert costs.roofline_seconds(197e12, 1e9, peak) == (1.0, "compute")
    t, bound = costs.roofline_seconds(1e9, 819e9, peak)
    assert bound == "hbm" and t == pytest.approx(1.0)


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError):
        costs.peaks("TPU v9")
    with pytest.raises(KeyError):
        costs.peaks("_source")
