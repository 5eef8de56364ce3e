"""The harness end to end on the CPU at tiny widths, its refusals, and the
shape of BENCHMARK.json."""
from __future__ import annotations

import json
import os
import re
import shutil
import subprocess
import time

import _bench_tiny
import jax
import numpy as np
import pytest

import run as bench_run
from benchlib import check, spec, traffic

ROOT = _bench_tiny.ROOT
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


@pytest.mark.parametrize("domains", [1, 2])
def test_sound_run_and_last_line(domains):
    res = bench_run.run_cell(_bench_tiny.cell(domains=domains), 2 ** 40 + 3,
                             0.3, False, jax.devices()[:1], time.time())
    assert list(res) == ["correct", "attempted", "failed", "metrics",
                         "device", "check"]
    assert res["correct"] is True and res["failed"] == 0
    assert res["attempted"] % 5 == 0 and res["attempted"] > 0
    assert set(res["metrics"]) == {"tokens_per_s", "setup_s"}
    assert all(m["value"] > 0 for m in res["metrics"].values())
    assert res["metrics"]["tokens_per_s"]["unit"] == "tokens/s"
    assert res["device"]["count"] == 1
    for k, v in res["check"].items():
        assert v["value"] <= v["limit"], k
    json.dumps(res)


def test_arguments_are_required():
    with pytest.raises(SystemExit):
        bench_run.main(["--workload", "phi4-g4-s2k"])
    with pytest.raises(SystemExit):
        bench_run.main(["--workload", "phi4-g4-s2k", "--seed", "1",
                        "--seconds", "10", "--trace", "2"])


def test_no_tpu_no_result(capsys):
    rc = bench_run.main(["--workload", "phi4-g4-s2k", "--seed", "1",
                         "--seconds", "1", "--trace", "0"])
    out = capsys.readouterr()
    assert rc == 1
    assert out.out == ""
    assert "no TPU" in out.err


def test_benchmark_files_alone_give_no_result(tmp_path):
    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        bm = json.load(fh)
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    for p in bm["paths"]:
        shutil.copytree(os.path.join(ROOT, p), tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    proc = subprocess.run(
        bm["command"] + ["--workload", bm["workloads"][0]["name"], "--seed",
                         "1", "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, capture_output=True, text=True, timeout=120,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert proc.returncode != 0
    assert proc.stdout == ""


def test_benchmark_json_names_files_that_exist():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        bm = json.load(fh)
    assert set(bm) == {"command", "paths", "run_seconds", "configs",
                       "workloads", "end_to_end", "per_layer"}
    names = [x["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in bm[k]]
    assert all(NAME.match(n) for n in names)
    assert len(set(names)) == len(names)
    for c in bm["configs"]:
        assert os.path.isfile(os.path.join(ROOT, c["file"]))
    metrics = {m["name"] for m in bm["end_to_end"]}
    assert "setup_s" in metrics
    for m in bm["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
    for w in bm["workloads"]:
        cell = spec.load_cell(w["name"], bm)
        assert cell.chips in (1, 4)
        assert cell.per_layer and len(cell.end_to_end) >= 2
        assert set(cell.settings["limits"]) == set(check.NUMBERS)
    for m in bm["per_layer"]:
        assert m["moves"] in metrics
        assert os.path.isfile(os.path.join(ROOT, "bench", "metrics",
                                           m["name"] + ".py"))
        assert callable(spec.metric_reader(m["name"]).read)


def test_pool_is_fixed_by_the_seed_and_split_into_domains():
    tr = {"generator": "zipf_rows", "seq": 256, "rows_per_group": 2,
          "zipf": 1.2, "domains": 2, "pool_epochs": 2}

    def pool(seed):
        return traffic.make_pool(traffic.run_keys(seed)[2], tr, vocab=512,
                                 T=3, groups=4)

    a, b = pool(2 ** 33 + 5), pool(2 ** 33 + 5)
    assert len(a) == 2 and a[0]["tokens"].shape == (3, 4, 2, 256)
    for x, y in zip(a, b):
        np.testing.assert_array_equal(x["tokens"], y["tokens"])
    assert not np.array_equal(a[0]["tokens"], pool(2 ** 33 + 6)[0]["tokens"])
    toks = np.asarray(a[0]["tokens"])
    np.testing.assert_array_equal(toks[..., 1:],
                                  np.asarray(a[0]["labels"])[..., :-1])
    for g in range(4):
        top = np.bincount(toks[:, g].ravel(), minlength=512).argmax()
        assert top == (g % 2) * 256           # each domain's most frequent id
