"""Pod-scale compile-artifact registry (docs/registry.md).

Covers the content-addressed store (atomic publish, CRC self-verify,
quarantine, torn-artifact invisibility, multi-writer races), the key
schema (program fingerprint × compile-environment identity), the sharded
warm scheduler (deterministic ownership, work stealing, per-program
outcomes), and the materialize integration: a registry-warmed fleet cold
start pays ZERO local compiles, and every registry failure mode degrades
to a local compile with bitwise-identical outputs.
"""

from __future__ import annotations

import importlib.util
import json
import os
import subprocess
import sys
import zlib

import numpy as np
import pytest
import torch

import torchdistx_tpu.config as tdx_config
from torchdistx_tpu import chaos, observe
from torchdistx_tpu.deferred_init import deferred_init
from torchdistx_tpu.jax_bridge import materialize_module_jax
from torchdistx_tpu import compile_service
from torchdistx_tpu.jax_bridge import materialize as mat
from torchdistx_tpu.registry import (
    ArtifactRegistry,
    registry_key,
    shard_owner,
    warm_sharded,
)
from torchdistx_tpu.registry import scheduler as sched
from torchdistx_tpu.registry import store as reg_store

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class Hetero(torch.nn.Module):
    """Distinct widths → several structural groups; small enough that
    every per-group program compiles in well under a second on CPU."""

    def __init__(self, k: int = 8):
        super().__init__()
        w = [16 + 8 * i for i in range(k)]
        self.layers = torch.nn.ModuleList(
            torch.nn.Linear(w[i], w[(i + 1) % k]) for i in range(k)
        )


@pytest.fixture(autouse=True)
def _cache_hygiene():
    """Every test binds its own cache/registry dirs; never leak a binding
    (or a chaos plan) into the next test."""
    os.environ["TDX_CACHE_MIN_COMPILE_S"] = "0"
    yield
    chaos.clear()
    compile_service.reset_cache_binding()
    os.environ.pop("TDX_CACHE_MIN_COMPILE_S", None)


@pytest.fixture
def counters():
    observe.enable(True)
    observe.reset()
    yield
    observe.reset()
    observe.enable(None)


def _snap():
    return {r["name"]: r["value"] for r in observe.counters().snapshot()
            if r["type"] == "counter"}


def _materialize(reg_dir, cache_dir, *, mode="auto", seed=0):
    compile_service.reset_cache_binding()
    with tdx_config.override(
        cache_dir=cache_dir, registry_dir=reg_dir,
        materialize_pipeline=mode, compile_workers=2,
    ):
        m = deferred_init(Hetero)
        params = materialize_module_jax(m, seed=seed)
    return ({k: np.asarray(v) for k, v in params.items()},
            mat.last_run_stats())


def _baseline(seed=0):
    compile_service.reset_cache_binding()
    with tdx_config.override(cache_dir=None, registry_dir=None,
                             materialize_pipeline="off"):
        m = deferred_init(Hetero)
        return {k: np.asarray(v)
                for k, v in materialize_module_jax(m, seed=seed).items()}


# ---------------------------------------------------------------------------
# store
# ---------------------------------------------------------------------------


class TestStore:
    def test_publish_fetch_roundtrip(self, tmp_path, counters):
        reg = ArtifactRegistry(str(tmp_path / "reg"))
        files = {"abc-cache": b"payload-bytes", "def-cache": b"more"}
        assert reg.publish("k" * 40, files, {"note": "t"})
        assert reg.has("k" * 40)
        got = reg.fetch("k" * 40)
        assert got == files
        meta = reg.read_meta("k" * 40)
        assert meta["note"] == "t"
        assert {r["name"] for r in meta["files"]} == set(files)
        snap = _snap()
        assert snap["tdx.registry.publish"] == 1
        assert snap["tdx.registry.fetch_hit"] == 1
        assert snap["tdx.registry.bytes_published"] == sum(
            len(v) for v in files.values()
        )
        assert snap["tdx.registry.bytes_fetched"] == snap[
            "tdx.registry.bytes_published"
        ]

    def test_fetch_absent_is_miss(self, tmp_path, counters):
        reg = ArtifactRegistry(str(tmp_path / "reg"))
        assert reg.fetch("0" * 40) is None
        assert _snap()["tdx.registry.fetch_miss"] == 1

    def test_republish_is_noop(self, tmp_path):
        reg = ArtifactRegistry(str(tmp_path / "reg"))
        assert reg.publish("k" * 40, {"a-cache": b"one"})
        assert not reg.publish("k" * 40, {"a-cache": b"two"})
        assert reg.fetch("k" * 40) == {"a-cache": b"one"}  # first wins

    def test_corrupt_payload_quarantined(self, tmp_path, counters):
        reg = ArtifactRegistry(str(tmp_path / "reg"))
        key = "c" * 40
        reg.publish(key, {"a-cache": b"x" * 64})
        victims = chaos.corrupt_registry_dir(reg.root, mode="flip")
        assert victims == [f"{key}/a-cache"]
        assert reg.fetch(key) is None
        assert not reg.has(key)
        assert os.path.isdir(reg.entry_dir(key) + ".corrupt")
        snap = _snap()
        assert snap["tdx.registry.verify_fail"] == 1
        assert snap["tdx.registry.fetch_miss"] == 1

    def test_truncated_payload_quarantined(self, tmp_path, counters):
        reg = ArtifactRegistry(str(tmp_path / "reg"))
        key = "d" * 40
        reg.publish(key, {"a-cache": b"y" * 128})
        chaos.corrupt_registry_dir(reg.root, mode="truncate")
        assert reg.fetch(key) is None
        assert _snap()["tdx.registry.verify_fail"] == 1

    def test_torn_manifest_quarantined(self, tmp_path, counters):
        reg = ArtifactRegistry(str(tmp_path / "reg"))
        key = "e" * 40
        edir = reg.entry_dir(key)
        os.makedirs(edir)
        with open(os.path.join(edir, "meta.json"), "w") as f:
            f.write('{"version": 1, "files": [{"na')  # torn write
        assert reg.fetch(key) is None
        assert os.path.isdir(edir + ".corrupt")
        assert _snap()["tdx.registry.verify_fail"] == 1

    def test_reader_never_sees_inflight_publish(self, tmp_path):
        # A publish in flight is a private .tmp-* dir: readers see a
        # plain miss, never a torn artifact — visibility IS the atomic
        # rename.
        reg = ArtifactRegistry(str(tmp_path / "reg"))
        key = "f" * 40
        tmp = os.path.join(reg.root, f".tmp-pub-{key[:16]}-999-1")
        os.makedirs(tmp)
        with open(os.path.join(tmp, "a-cache"), "wb") as f:
            f.write(b"half-written payload")
        assert not reg.has(key)
        assert reg.fetch(key) is None
        assert reg.keys() == []

    def test_unsafe_payload_names_refused(self, tmp_path, counters):
        reg = ArtifactRegistry(str(tmp_path / "reg"))
        assert not reg.publish("g" * 40, {"../evil-cache": b"x"})
        assert not reg.has("g" * 40)
        assert not (tmp_path / "evil-cache").exists()
        # A crafted manifest with a traversal name fails verification.
        key = "h" * 40
        edir = reg.entry_dir(key)
        os.makedirs(edir)
        with open(os.path.join(edir, "meta.json"), "w") as f:
            json.dump({"version": 1, "files": [
                {"name": "../../evil", "bytes": 1, "crc32": 0}
            ]}, f)
        assert reg.fetch(key) is None
        assert os.path.isdir(edir + ".corrupt")

    def test_fetch_into_cache_installs_and_shortcircuits(self, tmp_path,
                                                         counters):
        reg = ArtifactRegistry(str(tmp_path / "reg"))
        cdir = tmp_path / "cache"
        cdir.mkdir()
        key = "i" * 40
        data = b"executable-bytes" * 8
        reg.publish(key, {"zz-cache": data})
        assert reg.fetch_into_cache(key, str(cdir))
        assert (cdir / "zz-cache").read_bytes() == data
        snap = _snap()
        assert snap["tdx.registry.fetch_hit"] == 1
        # Second call: already installed → no further fetch traffic.
        assert reg.fetch_into_cache(key, str(cdir))
        assert _snap()["tdx.registry.fetch_hit"] == 1

    def test_concurrent_publish_single_winner_threads(self, tmp_path):
        import threading

        reg = ArtifactRegistry(str(tmp_path / "reg"))
        key = "j" * 40
        results = {}
        barrier = threading.Barrier(4)

        def racer(i):
            barrier.wait()
            results[i] = reg.publish(key, {"a-cache": bytes([i]) * 64})

        threads = [threading.Thread(target=racer, args=(i,))
                   for i in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert sum(results.values()) == 1  # exactly one winner
        got = reg.fetch(key)  # the surviving entry self-verifies
        assert got is not None and len(got["a-cache"]) == 64
        assert len(set(got["a-cache"])) == 1  # one writer's bytes, no mix
        leftovers = [n for n in os.listdir(reg.root)
                     if n.startswith(".tmp-")]
        assert leftovers == []  # losers cleaned up

    def test_concurrent_publish_single_winner_processes(self, tmp_path):
        # The cross-PROCESS version of the race: two interpreters publish
        # the same key with distinct payloads at the same moment; the
        # rename arbitration must leave exactly one complete, internally
        # consistent entry.
        reg_dir = str(tmp_path / "reg")
        go = str(tmp_path / "go")
        script = r"""
import os, sys, time, json
sys.path.insert(0, {repo!r})
from torchdistx_tpu.registry import ArtifactRegistry
tag = int(sys.argv[1])
reg = ArtifactRegistry({reg_dir!r})
while not os.path.exists({go!r}):
    time.sleep(0.001)
won = reg.publish("r" * 40, {{"a-cache": bytes([tag]) * 256}},
                  {{"tag": tag}})
print(json.dumps({{"tag": tag, "won": won}}))
""".format(repo=REPO, reg_dir=reg_dir, go=go)
        procs = [
            subprocess.Popen([sys.executable, "-c", script, str(tag)],
                             stdout=subprocess.PIPE, text=True,
                             env={**os.environ, "JAX_PLATFORMS": "cpu"})
            for tag in (7, 9)
        ]
        with open(go, "w") as f:
            f.write("go")
        outs = [json.loads(p.communicate(timeout=120)[0].strip())
                for p in procs]
        assert all(p.returncode == 0 for p in procs)
        wins = [o for o in outs if o["won"]]
        assert len(wins) == 1, outs
        reg = ArtifactRegistry(reg_dir)
        meta = reg.read_meta("r" * 40)
        got = reg.fetch("r" * 40)
        assert got is not None
        payload = got["a-cache"]
        # The entry is EXACTLY the winner's: payload matches its own
        # manifest CRC and is one process's bytes end to end.
        assert meta["tag"] == wins[0]["tag"]
        assert payload == bytes([meta["tag"]]) * 256
        assert zlib.crc32(payload) == meta["files"][0]["crc32"]


# ---------------------------------------------------------------------------
# key schema
# ---------------------------------------------------------------------------


class TestKeys:
    def test_registry_key_composes_env_identity(self, monkeypatch):
        fp = "ab" * 20
        k1 = registry_key(fp)
        monkeypatch.setattr(
            reg_store, "env_fingerprint",
            lambda: {"jax": "different-version"},
        )
        reg_store._reset_env_key()
        try:
            k2 = registry_key(fp)
        finally:
            monkeypatch.undo()
            reg_store._reset_env_key()
        assert k1 != k2
        assert registry_key(fp) == k1  # memo restored and deterministic

    def test_program_fp_stable_and_contract_sensitive(self):
        import jax.numpy as jnp

        m = deferred_init(Hetero)
        fakes = mat.named_fake_tensors(m)
        names, fake_list, osh = mat._names_and_shardings(fakes, None, None)
        mask = [True] * len(fake_list)
        idxs = list(range(4))
        fp1 = mat._registry_program_fp(fake_list, idxs, osh, None, mask)
        fp2 = mat._registry_program_fp(fake_list, idxs, osh, None, mask)
        assert fp1 == fp2  # deterministic
        fp_dtype = mat._registry_program_fp(
            fake_list, idxs, osh, jnp.bfloat16, mask
        )
        assert fp_dtype != fp1  # cast policy is part of the contract
        fp_other = mat._registry_program_fp(
            fake_list, [4, 5, 6, 7], osh, None, mask
        )
        assert fp_other != fp1  # different program

    def test_env_fingerprint_fields(self):
        info = reg_store.env_fingerprint()
        for field in ("jax", "jaxlib", "platform", "n_devices",
                      "compiler_options"):
            assert field in info, field
        assert info["platform"] == "cpu"


# ---------------------------------------------------------------------------
# scheduler
# ---------------------------------------------------------------------------


class TestScheduler:
    def test_shard_owner_partitions(self):
        keys = [registry_key(f"{i:040x}") for i in range(64)]
        for hosts in (1, 2, 3, 5):
            owners = [shard_owner(k, hosts) for k in keys]
            assert all(0 <= o < hosts for o in owners)
            if hosts > 1:
                assert len(set(owners)) > 1  # actually spreads
        # Pure function of the key: order/process independent.
        assert [shard_owner(k, 3) for k in keys] == [
            shard_owner(k, 3) for k in reversed(list(reversed(keys)))
        ]

    def test_single_host_local_outcomes(self, tmp_path):
        s = warm_sharded(Hetero, str(tmp_path / "cache"))
        assert s["programs"] >= 3
        assert s["unwarmed"] == []
        assert set(s["outcomes"]) == {"compiled"}  # no registry in play

    def test_publish_then_fetch_outcomes(self, tmp_path, counters):
        reg_dir = str(tmp_path / "reg")
        s0 = warm_sharded(Hetero, str(tmp_path / "c0"),
                          registry_dir=reg_dir)
        assert set(s0["outcomes"]) == {"published"}
        s1 = warm_sharded(Hetero, str(tmp_path / "c1"),
                          registry_dir=reg_dir)
        assert set(s1["outcomes"]) == {"fetched"}
        assert s1["programs"] == s0["programs"]

    def test_steal_when_owner_never_publishes(self, tmp_path, counters):
        reg_dir = str(tmp_path / "reg")
        s0 = warm_sharded(Hetero, str(tmp_path / "c0"),
                          registry_dir=reg_dir, hosts=2, host_id=0,
                          steal_after_s=0.0)
        assert s0["unwarmed"] == []
        assert s0["outcomes"].get("stolen", 0) >= 1
        assert _snap()["tdx.registry.steals"] == s0["outcomes"]["stolen"]
        # Everything (owned + stolen) was published: a late host 1 warms
        # entirely from the registry.
        s1 = warm_sharded(Hetero, str(tmp_path / "c1"),
                          registry_dir=reg_dir, hosts=2, host_id=1,
                          steal_after_s=60.0)
        assert set(s1["outcomes"]) == {"fetched"}

    def test_sharded_warm_requires_registry(self, tmp_path):
        with pytest.raises(ValueError, match="registry-dir"):
            warm_sharded(Hetero, str(tmp_path / "c"), hosts=2, host_id=0)
        with pytest.raises(ValueError, match="host_id"):
            warm_sharded(Hetero, str(tmp_path / "c"), hosts=2, host_id=2,
                         registry_dir=str(tmp_path / "r"))


# ---------------------------------------------------------------------------
# materialize integration
# ---------------------------------------------------------------------------


class TestMaterializeIntegration:
    def test_cold_start_zero_local_compiles(self, tmp_path, counters):
        base = _baseline(seed=5)
        reg_dir = str(tmp_path / "reg")
        a, st_a = _materialize(reg_dir, str(tmp_path / "c0"), seed=5)
        n = st_a["n_programs"]
        assert st_a["cache"] == {"miss": n}
        assert _snap()["tdx.registry.publish"] == n
        observe.reset()
        b, st_b = _materialize(reg_dir, str(tmp_path / "c1"), seed=5)
        snap = _snap()
        assert st_b["cache"] == {"hit": n}          # zero local compiles
        assert snap["tdx.registry.fetch_hit"] == n  # all registry fetches
        assert snap.get("tdx.jax.compile_cache_miss", 0) == 0
        for k in base:
            assert np.array_equal(base[k], a[k]), k
            assert np.array_equal(base[k], b[k]), k

    def test_monolithic_engine_uses_registry(self, tmp_path, counters):
        reg_dir = str(tmp_path / "reg")
        _materialize(reg_dir, str(tmp_path / "c0"), mode="off")
        assert _snap()["tdx.registry.publish"] == 1
        observe.reset()
        _, st = _materialize(reg_dir, str(tmp_path / "c1"), mode="off")
        assert st["cache"] == {"hit": 1}
        assert _snap()["tdx.registry.fetch_hit"] == 1

    def test_direct_serve_on_jax_key_mismatch(self, tmp_path, counters):
        # jax's cache key is not perfectly stable across traces and
        # processes; the registry's content address is.  Force the
        # mismatch: republish every artifact with its payload under a
        # name no consumer will ever compute — the local cache load must
        # miss, and the staged artifact must serve the executable
        # DIRECTLY (counted in tdx.registry.direct_serves), still zero
        # local compiles, still bitwise-equal.
        import shutil

        base = _baseline(seed=7)
        reg_dir = str(tmp_path / "reg")
        _, st = _materialize(reg_dir, str(tmp_path / "c0"), seed=7)
        n = st["n_programs"]
        reg = ArtifactRegistry(reg_dir)
        for key in reg.keys():
            files = reg.fetch(key)
            meta = reg.read_meta(key)
            shutil.rmtree(reg.entry_dir(key))
            renamed = {f"{key[:16]}{i:04x}-cache": data
                       for i, data in enumerate(files.values())}
            assert reg.publish(
                key, renamed, {"program_fp": meta.get("program_fp")}
            )
        observe.reset()
        b, st_b = _materialize(reg_dir, str(tmp_path / "c1"), seed=7)
        snap = _snap()
        assert st_b["cache"] == {"hit": n}
        assert snap["tdx.registry.direct_serves"] == n
        assert snap.get("tdx.jax.compile_cache_miss", 0) == 0
        for k in base:
            assert np.array_equal(base[k], b[k]), k

    def test_corrupt_registry_falls_back_and_heals(self, tmp_path,
                                                   counters):
        base = _baseline(seed=2)
        reg_dir = str(tmp_path / "reg")
        _, st = _materialize(reg_dir, str(tmp_path / "c0"), seed=2)
        n = st["n_programs"]
        chaos.corrupt_registry_dir(reg_dir, mode="flip")
        observe.reset()
        b, st_b = _materialize(reg_dir, str(tmp_path / "c1"), seed=2)
        snap = _snap()
        assert st_b["cache"] == {"miss": n}  # degraded to local compiles
        assert snap["tdx.registry.verify_fail"] == n
        corrupt = [e for e in os.listdir(reg_dir) if e.endswith(".corrupt")]
        assert len(corrupt) == n  # quarantined, kept for forensics
        # ...and HEALED: the local compiles republished clean artifacts.
        assert snap["tdx.registry.publish"] == n
        assert len(ArtifactRegistry(reg_dir).keys()) == n
        for k in base:
            assert np.array_equal(base[k], b[k]), k

    @pytest.mark.parametrize("plan_text", [
        "registry@1=raise;registry@2=raise",
        "registry@1=slow:0.05",
    ])
    def test_registry_chaos_degrades_bitwise(self, tmp_path, counters,
                                             plan_text):
        base = _baseline(seed=4)
        reg_dir = str(tmp_path / "reg")
        _materialize(reg_dir, str(tmp_path / "c0"), seed=4)
        chaos.install(chaos.parse_plan(plan_text))
        try:
            b, st = _materialize(reg_dir, str(tmp_path / "c1"), seed=4)
        finally:
            chaos.clear()
        assert sum(st["cache"].values()) == st["n_programs"]
        for k in base:
            assert np.array_equal(base[k], b[k]), k

    def test_registry_without_local_cache_is_inert(self, tmp_path,
                                                   counters):
        base = _baseline(seed=1)
        compile_service.reset_cache_binding()
        with tdx_config.override(cache_dir=None,
                                 registry_dir=str(tmp_path / "reg")):
            m = deferred_init(Hetero)
            params = materialize_module_jax(m, seed=1)
        snap = _snap()
        assert snap.get("tdx.registry.fetch_hit", 0) == 0
        assert snap.get("tdx.registry.publish", 0) == 0
        for k in base:
            assert np.array_equal(base[k], np.asarray(params[k])), k


# ---------------------------------------------------------------------------
# the CLI tool
# ---------------------------------------------------------------------------


class TestWarmCacheCLI:
    def _load_tool(self):
        spec = importlib.util.spec_from_file_location(
            "warm_cache_reg", os.path.join(REPO, "tools", "warm_cache.py")
        )
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod

    def test_per_program_reports_and_json(self, tmp_path, capsys):
        wc = self._load_tool()
        wc.main(["--model", "demo", "--cache-dir", str(tmp_path / "c"),
                 "--registry-dir", str(tmp_path / "r"), "--skip-whole"])
        out = capsys.readouterr()
        summary = json.loads(out.out.strip().splitlines()[-1])
        assert summary["programs"] >= 2
        assert summary["unwarmed"] == []
        assert set(summary["outcomes"]) == {"published"}
        reports = summary["program_reports"]
        assert len(reports) == summary["programs"]
        assert all(r["outcome"] == "published" for r in reports)
        warm_lines = [ln for ln in out.err.splitlines()
                      if ln.startswith("warm: program=")]
        assert len(warm_lines) == len(reports)

    def test_unwarmed_program_exits_nonzero(self, tmp_path, capsys,
                                            monkeypatch):
        wc = self._load_tool()

        def boom(*a, **k):
            raise RuntimeError("injected compile failure")

        monkeypatch.setattr(compile_service, "compile_program", boom)
        with pytest.raises(SystemExit) as exc:
            wc.main(["--model", "demo",
                     "--cache-dir", str(tmp_path / "c")])
        assert exc.value.code == 1
        summary = json.loads(
            capsys.readouterr().out.strip().splitlines()[-1]
        )
        assert summary["unwarmed"]
        assert all(r["outcome"] == "unwarmed"
                   for r in summary["program_reports"])


# ---------------------------------------------------------------------------
# cross-process acceptance (the registry-smoke contract, in pytest form)
# ---------------------------------------------------------------------------


@pytest.mark.slow
class TestTwoProcessShardedWarm:
    def test_disjoint_shards_then_all_hit_cold_start(self, tmp_path):
        reg_dir = str(tmp_path / "reg")
        env = {**os.environ, "JAX_PLATFORMS": "cpu",
               "TDX_CACHE_MIN_COMPILE_S": "0"}
        procs = []
        for host in (0, 1):
            menv = dict(env)
            menv["TDX_METRICS_PATH"] = str(tmp_path / f"m{host}.jsonl")
            procs.append(subprocess.Popen(
                [sys.executable, os.path.join(REPO, "tools",
                                              "warm_cache.py"),
                 "--model", "demo",
                 "--cache-dir", str(tmp_path / f"c{host}"),
                 "--registry-dir", reg_dir,
                 "--hosts", "2", "--host-id", str(host),
                 "--steal-after", "300"],
                stdout=subprocess.PIPE, stderr=subprocess.PIPE,
                text=True, cwd=REPO, env=menv,
            ))
        outs = [p.communicate(timeout=360) for p in procs]
        assert all(p.returncode == 0 for p in procs), [o[1] for o in outs]
        summaries = [json.loads(o[0].strip().splitlines()[-1])
                     for o in outs]
        compiled = []
        for host, s in enumerate(summaries):
            assert s["unwarmed"] == []
            own = {r["program"] for r in s["program_reports"]
                   if r["outcome"] in ("published", "compiled", "stolen")}
            compiled.append(own)
            # EXACT per-process compile counters: the flushed metrics
            # must show exactly |owned| local compiles, zero more.
            with open(tmp_path / f"m{host}.jsonl") as f:
                recs = [json.loads(ln) for ln in f if ln.strip()]
            miss = sum(r["value"] for r in recs
                       if r["name"] == "tdx.jax.compile_cache_miss")
            assert miss == len(own), (host, miss, own)
        assert not (compiled[0] & compiled[1])  # disjoint
        every = {r["program"] for s in summaries
                 for r in s["program_reports"]}
        assert compiled[0] | compiled[1] == every  # covering

        # Fresh process, EMPTY local cache: zero local compiles, all
        # registry fetches, bitwise-equal to the registry-free path.
        check = (
            "import json, numpy as np, torch;"
            "from torchdistx_tpu.deferred_init import deferred_init;"
            "from torchdistx_tpu.jax_bridge import materialize_module_jax;"
            "import torchdistx_tpu.config as tdx_config;"
            "from torchdistx_tpu import compile_service, observe;"
            "w=[32+8*i for i in range(12)];\n"
            "class Demo(torch.nn.Module):\n"
            "    def __init__(self):\n"
            "        super().__init__();"
            "        self.layers=torch.nn.ModuleList("
            "torch.nn.Linear(w[i], w[(i+1)%len(w)])"
            " for i in range(len(w)))\n"
            "p=materialize_module_jax(deferred_init(Demo), seed=0);"
            "s={r['name']: r['value'] for r in"
            " observe.counters().snapshot() if r['type']=='counter'};"
            "assert s.get('tdx.jax.compile_cache_miss', 0)==0, s;"
            "assert s.get('tdx.registry.fetch_hit', 0)=="
            "s.get('tdx.jax.compile_cache_hit', 0)>0, s;"
            "compile_service.reset_cache_binding();\n"
            "with tdx_config.override(cache_dir=None, registry_dir=None,"
            " materialize_pipeline='off'):\n"
            "    b=materialize_module_jax(deferred_init(Demo), seed=0)\n"
            "assert all(np.array_equal(np.asarray(b[k]),"
            " np.asarray(p[k])) for k in b);"
            "print('COLD-START-OK')"
        )
        fresh_env = dict(env)
        fresh_env["TDX_CACHE_DIR"] = str(tmp_path / "fresh")
        fresh_env["TDX_REGISTRY_DIR"] = reg_dir
        fresh_env["TDX_METRICS_PATH"] = str(tmp_path / "fresh.jsonl")
        r = subprocess.run([sys.executable, "-c", check],
                           capture_output=True, text=True, cwd=REPO,
                           env=fresh_env, timeout=360)
        assert r.returncode == 0, r.stderr
        assert "COLD-START-OK" in r.stdout
