"""Packaging sanity: Helm values/template consistency and Docker invariants.

The reference ships an unparameterized app (its Helm values never reach the
process, SURVEY.md §5); here the chart wires LFKT_* env vars, so these tests
pin (a) every `.Values.x.y` referenced by a template exists in values.yaml,
(b) the env names the chart sets are ones utils/config.py actually reads,
and (c) the image has no CUDA and exactly one worker (the load-bearing
`-w 1`, reference docker/Dockerfile.app:12).
"""

from __future__ import annotations

import glob
import os
import re

import yaml

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _values():
    with open(os.path.join(REPO, "helm", "values.yaml")) as f:
        return yaml.safe_load(f)


def _lookup(values: dict, dotted: str) -> bool:
    node = values
    for part in dotted.split("."):
        if not isinstance(node, dict) or part not in node:
            return False
        node = node[part]
    return True


def test_all_template_values_exist():
    values = _values()
    missing = []
    for path in glob.glob(os.path.join(REPO, "helm", "templates", "*.yaml")):
        text = open(path).read()
        for ref in set(re.findall(r"\.Values\.([A-Za-z0-9_.]+)", text)):
            if not _lookup(values, ref):
                missing.append((os.path.basename(path), ref))
    assert not missing, f"templates reference undefined values: {missing}"


def test_chart_env_vars_are_read_by_config():
    cfg_src = open(os.path.join(
        REPO, "llama_fastapi_k8s_gpu_tpu", "utils", "config.py")).read()
    known = set(re.findall(r'"(LFKT_[A-Z0-9_]+)"', cfg_src))
    dep = open(os.path.join(REPO, "helm", "templates", "deployment.yaml")).read()
    used = set(re.findall(r"name: (LFKT_[A-Z0-9_]+)", dep))
    assert used, "deployment should set LFKT_* env vars"
    assert used <= known, f"chart sets env vars config.py never reads: {used - known}"


def test_reference_behavior_defaults_preserved():
    """Queue(5), 25s timeout, n_ctx 1024 — reference api.py:17-19 — are the
    chart defaults too."""
    values = _values()
    assert values["app"]["maxContextTokens"] == 1024
    assert values["app"]["timeoutSeconds"] == 25
    assert values["app"]["maxQueueSize"] == 5
    assert values["replicaCount"] == 4  # reference values.yaml:17


def test_probes_hit_health():
    dep = open(os.path.join(REPO, "helm", "templates", "deployment.yaml")).read()
    for probe in ("startupProbe", "readinessProbe", "livenessProbe"):
        assert probe in dep, f"{probe} missing (reference README advertises probes)"
    assert dep.count("path: /health") == 3


def test_docker_zero_cuda_single_worker():
    base = open(os.path.join(REPO, "docker", "Dockerfile.base")).read()
    app = open(os.path.join(REPO, "docker", "Dockerfile.app")).read()
    base_code = "\n".join(  # comments may cite the reference's CUDA setup
        ln for ln in base.splitlines() if not ln.strip().startswith("#"))
    for forbidden in ("nvidia", "cuda", "cublas"):
        assert forbidden not in base_code.lower()
    assert "jax[tpu]" in base
    assert "llama_fastapi_k8s_gpu_tpu.server" in app  # single-worker entrypoint
    assert "EXPOSE 8000" in base  # reference Dockerfile.base:34
