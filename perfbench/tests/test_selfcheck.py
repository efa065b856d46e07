import json
import os

import pytest

import run as bench
from harness import family, selfcheck, stats, traffic

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def test_selfcheck_passes():
    selfcheck.run()


def test_percentile():
    assert stats.percentile([1, 2, 3, 4, 5], 50) == 3
    assert stats.percentile([0, 10], 90) == pytest.approx(9.0)


def test_every_seed_gets_the_same_work():
    mix = {"rate_per_s": 2.0,
           "prompt_len": {"dist": "lognormal", "median": 64, "sigma": 1.0,
                          "min": 8, "max": 256},
           "output_len": {"dist": "uniform", "min": 1, "max": 4}}
    a = traffic.open_poisson(mix, 1000, 1, 20.0)
    b = traffic.open_poisson(mix, 1000, 3000000019, 20.0)
    assert sorted(len(r["prompt"]) for r in a) \
        == sorted(len(r["prompt"]) for r in b)
    assert [len(r["prompt"]) for r in a] != [len(r["prompt"]) for r in b]
    again = traffic.open_poisson(mix, 1000, 3000000019, 20.0)
    assert [r["due"] for r in again] == [r["due"] for r in b]
    assert all((x["prompt"] == y["prompt"]).all()
               for x, y in zip(again, b))
    assert sorted(round(r["due"], 9) for r in a) \
        != sorted(round(r["due"], 9) for r in b)
    fixed = dict(mix, schedule_seed=7)
    c = traffic.open_poisson(fixed, 1000, 1, 20.0)
    d = traffic.open_poisson(fixed, 1000, 2, 20.0)
    assert [r["due"] for r in c] == [r["due"] for r in d]
    assert [len(r["prompt"]) for r in c] == [len(r["prompt"]) for r in d]
    assert any((x["prompt"] != y["prompt"]).any() for x, y in zip(c, d))


def test_benchmark_json_names_files_that_exist():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        spec = json.load(f)
    for c in spec["configs"]:
        assert os.path.isfile(os.path.join(ROOT, c["file"]))
        with open(os.path.join(ROOT, c["file"])) as f:
            assert os.path.isfile(family.path_of(json.load(f)["family"]))
    for w in spec["workloads"]:
        assert os.path.isfile(os.path.join(
            ROOT, "perfbench", "mixes", w["traffic"] + ".json"))
    for m in spec["per_layer"]:
        assert os.path.isfile(bench.reader_path(m["name"]))
