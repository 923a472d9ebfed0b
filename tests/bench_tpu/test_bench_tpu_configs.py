"""A configuration file states the store it deploys.

`harness.build_store` builds the `SLSMParams`, the compaction policy and
the log that the file states, and refuses a key or a policy it does not
know. A leveled store, the next deployment the benchmark is to take,
runs through the benchmark's own `run_cell` at tiny sizes.
"""
import json
import os
import shutil
import subprocess
import sys

import pytest

import repro.engine
from bench_tpu_tiny import ROOT, correct, run_config, tiny_cell
from bench_tpu import harness as H
from repro.core.params import SLSMParams, TuningPolicy
from repro.engine import LevelingPolicy, TieringPolicy

# the SLSMParams fields the harness passed before a configuration could
# state any field: the committed configurations build what they built
FIRST_KEYS = ("R", "Rn", "eps", "D", "m", "mu", "max_levels",
              "merge_budget", "range_cand", "max_range", "backend")
LEVELED = {"name": "leveling", "max_resident": 2}


class Captured:
    """Takes `SLSM`'s place: keeps what the store would be built from."""

    def __init__(self, params, policy=None, durability=None):
        self.params, self.policy, self.durability = (params, policy,
                                                     durability)


@pytest.fixture
def captured(monkeypatch):
    monkeypatch.setattr(repro.engine, "SLSM", Captured)


def config_file(name: str) -> dict:
    return json.loads((ROOT / "bench_tpu" / "configs" /
                       f"{name}.json").read_text())


@pytest.mark.parametrize("name", ["s3-durable", "s3-volatile"])
def test_committed_configs_build_the_same_store(captured, tmp_path, name):
    config = H.Registry().config(name)
    store = H.build_store(config, str(tmp_path / "wal"), False)
    assert store.params == SLSMParams(**{k: config[k] for k in FIRST_KEYS})
    assert type(store.policy) is TieringPolicy
    dur = config["durability"]
    if dur is None:
        assert store.durability is None
        return
    try:
        assert store.durability.dir == tmp_path / "wal"
        assert store.durability.fsync is dur["fsync"] is True
        assert (store.durability.snapshot_every_bytes
                == dur["snapshot_every_bytes"] == 1 << 28)
    finally:
        store.durability.close()


def test_a_configuration_states_its_policy_and_tuning(captured):
    config = dict(config_file("s3-volatile"), policy=LEVELED,
                  eps_per_level=[1e-3, 1e-4],
                  tuning={"mode": "adaptive", "interval": 64})
    store = H.build_store(config, None, False)
    assert type(store.policy) is LevelingPolicy
    assert store.policy.max_resident == 2
    assert store.params.eps_per_level == (1e-3, 1e-4)
    assert store.params.tuning == TuningPolicy(mode="adaptive", interval=64)
    store = H.build_store(dict(config, policy={"name": "tiering"}), None,
                          False)
    assert type(store.policy) is TieringPolicy


@pytest.mark.parametrize("edit,named", [
    ({"max_resident": 2}, "max_resident"),
    ({"preload_key": 10}, "preload_key"),
    ({"policy": {"name": "sideways"}}, "sideways"),
    ({"policy": {"name": "tiering", "max_resident": 2}}, "tiering"),
    ({"policy": "leveling"}, "leveling"),
])
def test_what_the_harness_does_not_know_is_refused(tmp_path, edit, named):
    config = dict(config_file("s3-volatile"), **edit)
    for control in (False, True):
        with pytest.raises(H.BenchError, match=named):
            H.build_store(config, None, control)
    (tmp_path / "c.json").write_text(json.dumps(config))
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(
        {"configs": [{"name": "c", "file": "c.json"}]}))
    with pytest.raises(H.BenchError, match=named):
        H.Registry(tmp_path).config("c")


@pytest.mark.parametrize("edit,named", [
    ({"max_resident": 2}, "max_resident"),
    ({"policy": {"name": "sideways"}}, "sideways"),
])
def test_the_command_refuses_a_configuration_it_does_not_know(
        tmp_path, edit, named):
    shutil.copytree(ROOT / "bench_tpu", tmp_path / "bench_tpu",
                    ignore=shutil.ignore_patterns("testdata", "__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    path = tmp_path / "bench_tpu" / "configs" / "s3-volatile.json"
    path.write_text(json.dumps(dict(config_file("s3-volatile"), **edit)))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=str(ROOT / "src"),
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"))
    p = subprocess.run(
        [sys.executable, str(tmp_path / "bench_tpu" / "run.py"),
         "--workload", "lookup.s3-volatile", "--seed", "1", "--seconds",
         "1", "--trace", "0"], cwd=tmp_path, env=env, capture_output=True,
        text=True, timeout=120)
    assert p.returncode == 2
    assert p.stdout.strip() == ""
    assert named in p.stderr


@pytest.mark.parametrize("traffic,named", [
    ({"loop": "closed", "op": "delete"}, "'delete'"),
    ({"loop": "burst"}, "'burst'"),
])
def test_tiny_sizes_refuse_traffic_the_generator_does_not_read(
        monkeypatch, traffic, named):
    monkeypatch.setattr(H.Registry, "traffic", lambda self, name: traffic)
    with pytest.raises(ValueError, match=named):
        tiny_cell("ingest.s3-volatile")


def leveled(traffic_name: str):
    """s3-volatile under leveled compaction at tiny sizes, with the
    traffic of `traffic_name`."""
    config, traffic = tiny_cell(f"{traffic_name}.s3-volatile")
    return dict(config, policy=LEVELED), traffic


def test_a_leveled_store_ingests_correctly_and_compacts():
    run, checks = run_config("ingest.s3-leveled", *leveled("ingest"))
    assert correct(run, checks), (checks, run.error)
    assert run.attempted > 0
    stats = run.stats_after
    assert stats["spills"] > 0 and stats["compactions"] >= 1
    # level 0 spills at every second flush (max_resident 2), where
    # tiering waits for D = 4 runs
    assert stats["spills"] >= stats["flushes"] // 2


def test_a_leveled_store_serves_lookups_correctly():
    run, checks = run_config("lookup.s3-leveled", *leveled("lookup"))
    assert correct(run, checks), (checks, run.error)
    assert run.attempted > 0


def test_the_control_in_a_leveled_store_is_not_correct():
    run, checks = run_config("ingest.s3-leveled", *leveled("ingest"),
                             control=True)
    assert run.error is None
    assert not correct(run, checks), checks
