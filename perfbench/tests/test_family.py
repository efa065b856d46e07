"""The harness learns a configuration's model family from data: the
interface of ``harness/family.py``, a second family laid in a temporary
directory that a whole rehearsal runs through without an edit under
``perfbench/``, and the failures of a configuration that names none."""

import glob
import json
import os

import pytest

import run as bench
from harness import family, serve, train

PERFBENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CHAT = "mistral-7b-v0.3-l16.chat-open"
TRAIN = "internlm2-1.8b-l4.pretrain-4k"

# a thin wrapper of ``decoder`` that records which names the harness calls
RECORDER = '''
import importlib.util
_spec = importlib.util.spec_from_file_location("recorded_decoder", {path!r})
_inner = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_inner)
CALLS = []


def _recorded(name):
    def call(*args, **kw):
        CALLS.append(name)
        return getattr(_inner, name)(*args, **kw)
    return call


for _name in {names!r}:
    globals()[_name] = _recorded(_name)
'''


def test_every_family_defines_the_whole_interface():
    files = glob.glob(os.path.join(family.DIRECTORY, "*.py"))
    assert files
    for path in files:
        name = os.path.basename(path)[:-3]
        mod = family.load({"family": name}, "a test")
        assert all(callable(getattr(mod, n)) for n in family.REQUIRED)


def test_a_module_lacking_a_name_fails_at_load(tmp_path, monkeypatch):
    (tmp_path / "half.py").write_text("def build_model(cfg, dtype): pass\n")
    monkeypatch.setattr(family, "DIRECTORY", str(tmp_path))
    with pytest.raises(AttributeError, match="half.py lacks leaves"):
        family.load({"family": "half"}, "a test")


def name_family(monkeypatch, edit):
    """Every configuration file read from now on has ``edit`` applied."""
    real = bench.load_json

    def load_json(*parts):
        doc = real(*parts)
        if "configs" in os.path.join(*parts).split("/"):
            edit(doc)
        return doc

    monkeypatch.setattr(bench, "load_json", load_json)


def drive(cell, seconds):
    code, result = bench.execute(["--workload", cell, "--seed", "3000000013",
                                  "--seconds", seconds, "--rehearse", "1"])
    assert code == bench.REHEARSAL_EXIT
    return result


@pytest.mark.parametrize("cell,seconds,reached", [
    (CHAT, "4", {"build_model", "leaves", "ends", "layer", "layer_count",
                 "engine", "release", "total_params", "served_logits",
                 "selfcheck"}),
    (TRAIN, "2", {"build_model", "leaves", "ends", "layer", "layer_count",
                  "total_params", "train_flops_per_token", "train_readings",
                  "selfcheck"})], ids=["serving", "training"])
def test_a_second_family_arrives_as_new_files(cell, seconds, reached,
                                              tmp_path, monkeypatch):
    (tmp_path / "recorder.py").write_text(RECORDER.format(
        path=family.path_of("decoder"), names=family.REQUIRED))
    monkeypatch.setattr(family, "DIRECTORY", str(tmp_path))
    name_family(monkeypatch, lambda doc: doc.update(family="recorder"))
    loaded, real = [], family.load
    monkeypatch.setattr(family, "load", lambda cfg, where: loaded.append(
        real(cfg, where)) or loaded[-1])
    result = drive(cell, seconds)
    assert result["correct"], result
    assert len(loaded) == 1
    assert loaded[0].__file__ == str(tmp_path / "recorder.py")
    assert reached <= set(loaded[0].CALLS), sorted(set(loaded[0].CALLS))


def test_the_harness_names_no_family_and_no_model():
    """What lies outside ``families/`` reaches model facts through
    ``run.family`` alone: it imports no model of the program's, no
    family's module, and reads none of a decoder's keys."""
    words = ("paddle_tpu.models", "families.", "import decoder", "Llama",
             "q_proj", "num_key_value_heads", "intermediate_size",
             "num_hidden_layers", "k_pools")
    files = [os.path.join(PERFBENCH, "run.py")] + glob.glob(
        os.path.join(PERFBENCH, "harness", "*.py"))
    for path in files:
        with open(path) as f:
            text = f.read()
        found = [w for w in words if w in text]
        assert not found, (path, found)


@pytest.mark.parametrize("edit,error,said", [
    (lambda doc: doc.update(family="no-such-family"), FileNotFoundError,
     os.path.join("families", "no-such-family.py")),
    (lambda doc: doc.pop("family"), KeyError,
     os.path.join("families", "<family>.py"))])
def test_a_configuration_without_a_family_fails_before_anything_is_built(
        edit, error, said, monkeypatch):
    name_family(monkeypatch, edit)

    def built(run):
        raise AssertionError("the driver was reached")

    monkeypatch.setattr(serve, "run", built)
    monkeypatch.setattr(train, "run", built)
    with pytest.raises(error) as exc:
        bench.execute(["--workload", CHAT, "--seed", "1", "--rehearse", "1"])
    assert said in str(exc.value)
    assert "perfbench/configs/mistral-7b-v0.3-l16.json" in str(exc.value)


def test_rehearsal_sizes_are_the_configurations():
    """A mix holds no model size: a new configuration runs under a mix
    that is there as pure data."""
    for path in glob.glob(os.path.join(PERFBENCH, "mixes", "*.json")):
        with open(path) as f:
            assert "config" not in json.load(f).get("rehearse", {}), path
    for path in glob.glob(os.path.join(PERFBENCH, "configs", "*.json")):
        with open(path) as f:
            doc = json.load(f)
        assert doc["family"] and doc["rehearse"]["vocab_size"], path
