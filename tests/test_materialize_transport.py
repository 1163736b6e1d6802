"""Streaming materialize transport (docs/performance.md §transport).

Covers the ISSUE-9 transport layer: the donation/overlap/batching knob
parity matrix against a fault-free monolith, the batched per-sharding
``device_put`` helper (and the resume path riding it), the donated
commit program's aliasing/consumption semantics and its retry ladder
(consumed donated inputs regenerate via the producer; the final rung
compiles non-donating), the ``TDX_MATERIALIZE_INIT_DTYPE=bf16`` fast
path's two-tier parity contract (exact-bitwise where the contract dtype
already is bf16; exactly-the-bf16-rounding-of-default otherwise), the
chaos ``execute`` site with donation enabled, and the swept link probe.

Kept lean for tier-1: one small recorded model shared per scenario
family, one persistent-cache dir for the whole module (everything after
the first compile of each program set is a warm hit), multi-second
cases ``slow``-marked (``make chaos-test`` runs them).
"""

import numpy as np
import pytest
import torch

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec

import torchdistx_tpu.config as tdx_config
from torchdistx_tpu import compile_service, observe, transport
from torchdistx_tpu.deferred_init import deferred_init
from torchdistx_tpu.jax_bridge import materialize as mat
from torchdistx_tpu.jax_bridge import materialize_module_jax

K = 10  # layers; distinct widths defeat batching → a real multi-group split


class Pyramid(torch.nn.Module):
    def __init__(self):
        super().__init__()
        w = [8 + 4 * i for i in range(K)]
        self.layers = torch.nn.ModuleList(
            torch.nn.Linear(w[i], w[(i + 1) % K]) for i in range(K)
        )
        # An f32 BUFFER: ineligible for the init-dtype cast, so under
        # the bf16 transport it rides the donated commit program as a
        # pass-through slot (the aliasing case).
        self.register_buffer("scale", torch.ones(64))


@pytest.fixture(scope="module")
def cache_dir(tmp_path_factory):
    d = tmp_path_factory.mktemp("transport_cache")
    compile_service.reset_cache_binding()
    yield str(d)
    compile_service.reset_cache_binding()


def _run(mode, cache_dir, *, seed=0, param_dtype=None, **kw):
    with tdx_config.override(
        materialize_pipeline=mode, cache_dir=cache_dir, **kw
    ):
        m = deferred_init(Pyramid)
        vals = materialize_module_jax(m, seed=seed, param_dtype=param_dtype)
    return {k: np.asarray(v) for k, v in vals.items()}


def _assert_bitwise(a, b):
    assert set(a) == set(b)
    for k in a:
        assert a[k].dtype == b[k].dtype, k
        assert np.array_equal(a[k], b[k]), k


@pytest.fixture(scope="module")
def ref(cache_dir):
    """Fault-free monolith, default transport config — THE oracle."""
    return _run("off", cache_dir)


@pytest.fixture(scope="module")
def ref_bf16(cache_dir):
    """Fault-free monolith under the bf16 init fast path."""
    return _run("off", cache_dir, materialize_init_dtype="bf16")


# -- knob parity matrix -------------------------------------------------------


@pytest.mark.parametrize("mode", ["off", "auto"])
@pytest.mark.parametrize("donate", [True, False])
@pytest.mark.parametrize("depth", [1, 3])
def test_parity_matrix(mode, donate, depth, ref, cache_dir):
    """Donation on/off × overlap depth × both engines: bitwise-equal to
    the fault-free monolith (the knobs change how bytes move, never
    which bits land)."""
    vals = _run(mode, cache_dir, materialize_donate=donate,
                materialize_overlap_depth=depth)
    _assert_bitwise(vals, ref)


def test_per_leaf_transfer_parity(ref, cache_dir):
    """The batching escape hatch (TDX_MATERIALIZE_BATCH_PUT=0) changes
    dispatch count only, never values."""
    vals = _run("auto", cache_dir, materialize_batch_put=False)
    _assert_bitwise(vals, ref)


def test_pipelined_engine_engaged(cache_dir):
    """The module's model must actually exercise the pipelined engine —
    otherwise the matrix above silently tests the monolith twice."""
    _run("auto", cache_dir)
    stats = mat.last_run_stats()
    assert stats["mode"] == "pipelined"
    assert stats["n_programs"] >= 2
    for key in ("bytes_donated", "transfer_overlap", "device_put_batches"):
        assert key in stats


# -- batched per-sharding device_put ------------------------------------------


def test_batched_device_put_groups_by_sharding():
    mesh = Mesh(np.array(jax.devices()[:2]).reshape(2), ("d",))
    s_rep = NamedSharding(mesh, PartitionSpec())
    s_shard = NamedSharding(mesh, PartitionSpec("d"))
    arrs = [
        np.arange(4, dtype=np.float32),
        np.arange(8, dtype=np.float32),
        np.ones(6, dtype=np.int32),
        np.full(8, 7.0, dtype=np.float32),
    ]
    shardings = [s_rep, s_shard, s_rep, s_shard]
    c0 = observe.counter("tdx.jax.device_put_batches").value
    vals, n = transport.batched_device_put(arrs, shardings)
    assert n == 2  # one dispatch per distinct sharding
    assert observe.counter("tdx.jax.device_put_batches").value - c0 == 2
    for v, a, s in zip(vals, arrs, shardings):
        assert np.array_equal(np.asarray(v), a)
        assert v.sharding == s


def test_batched_device_put_no_shardings_single_batch():
    vals, n = transport.batched_device_put(
        [np.arange(3, dtype=np.float32), np.ones(2, dtype=np.float32)]
    )
    assert n == 1
    assert np.array_equal(np.asarray(vals[0]), [0, 1, 2])


def test_resume_group_batched_vs_per_leaf(tmp_path):
    """_try_resume_group loads a committed group in ONE batched dispatch
    per distinct sharding (the materialize.py:1107 satellite), per-leaf
    only under the escape hatch — same values either way."""
    mesh = Mesh(np.array(jax.devices()[:2]).reshape(2), ("d",))
    osh = [NamedSharding(mesh, PartitionSpec())] * 3
    values = [np.arange(6, dtype=np.float32) + i for i in range(3)]
    rdir = str(tmp_path)
    manifest = {}
    mat._commit_resume_group(rdir, manifest, "a" * 40, [0, 1, 2],
                             values)
    rec = manifest["a" * 40]
    c0 = observe.counter("tdx.jax.device_put_batches").value
    loaded = mat._try_resume_group(rdir, "a" * 40, rec, [0, 1, 2], osh,
                                   batch_put=True)
    assert loaded is not None
    vals, n = loaded
    assert n == 1  # all three share one sharding → one dispatch
    assert observe.counter("tdx.jax.device_put_batches").value - c0 == 1
    for v, a in zip(vals, values):
        assert np.array_equal(np.asarray(v), a)
    vals2, n2 = mat._try_resume_group(rdir, "a" * 40, rec, [0, 1, 2], osh,
                                      batch_put=False)
    assert n2 == 0
    for v, a in zip(vals2, values):
        assert np.array_equal(np.asarray(v), a)


# -- donated commit program ---------------------------------------------------


def _toy_plan_and_outs():
    mesh = Mesh(np.array(jax.devices()[:1]), ("d",))
    sh = NamedSharding(mesh, PartitionSpec())
    plan = transport.plan_transport(
        [jnp.float32, jnp.float32], [True, False], jnp.bfloat16, [sh, sh]
    )

    def producer():
        return (
            jax.device_put(jnp.arange(8, dtype=jnp.bfloat16), sh),
            jax.device_put(jnp.ones(4, dtype=jnp.float32), sh),
        )

    return plan, producer


def test_commit_donation_aliases_and_consumes():
    """With donation, a pass-through slot aliases its buffer (pointer
    equality — the 'no defensive copy' assertion) and is consumed
    (is_deleted); a converting slot upcasts to its contract dtype.
    Donated bytes are counted."""
    plan, producer = _toy_plan_and_outs()
    outs = producer()
    passthrough = outs[1]
    p_in = passthrough.unsafe_buffer_pointer()
    c0 = observe.counter("tdx.jax.bytes_donated").value
    final, donated = transport.commit_outputs(
        outs, plan, donate=True, producer=producer, retries=2,
        retryable=(),
    )
    assert final[0].dtype == jnp.float32
    assert np.array_equal(np.asarray(final[0]), np.arange(8))
    assert passthrough.is_deleted()
    assert final[1].unsafe_buffer_pointer() == p_in
    assert donated >= passthrough.size * 4
    assert observe.counter("tdx.jax.bytes_donated").value - c0 == donated


def test_commit_without_donation_leaves_passthrough_untouched():
    plan, producer = _toy_plan_and_outs()
    outs = producer()
    final, donated = transport.commit_outputs(
        outs, plan, donate=False, producer=producer, retries=0,
        retryable=(),
    )
    assert donated == 0
    assert not outs[1].is_deleted()
    assert final[1] is outs[1]  # never entered the commit program
    assert final[0].dtype == jnp.float32


def test_commit_retry_regenerates_consumed_inputs():
    """A donated buffer must not be consumed twice: feeding already-
    consumed outputs re-runs the producer (idempotent — the PRNG key is
    never donated)."""
    plan, producer = _toy_plan_and_outs()
    calls = []

    def counting_producer():
        calls.append(1)
        return producer()

    outs = producer()
    transport.commit_outputs(outs, plan, donate=True,
                             producer=counting_producer, retries=2,
                             retryable=(RuntimeError,))
    # `outs` are now consumed; committing them again must regenerate.
    final, _ = transport.commit_outputs(
        outs, plan, donate=True, producer=counting_producer, retries=2,
        retryable=(RuntimeError,),
    )
    assert len(calls) == 1
    assert np.array_equal(np.asarray(final[0]), np.arange(8))


def test_commit_final_retry_non_donating(monkeypatch):
    """Donation itself must never be able to fail every rung: the final
    retry compiles a non-donating commit program."""
    plan, producer = _toy_plan_and_outs()
    orig = transport._commit_program
    donate_calls = []

    def failing_donating(shapes, src, dst, osh, donate):
        if donate:
            donate_calls.append(1)
            raise RuntimeError("injected: donating commit rejected")
        return orig(shapes, src, dst, osh, donate)

    monkeypatch.setattr(transport, "_commit_program", failing_donating)
    c0 = observe.counter("tdx.jax.commit_retries").value
    final, donated = transport.commit_outputs(
        producer(), plan, donate=True, producer=producer, retries=2,
        retryable=(RuntimeError,),
    )
    assert donated == 0  # delivered by the non-donating rung
    assert len(donate_calls) == 2  # attempts 0 and 1 tried donation
    assert observe.counter("tdx.jax.commit_retries").value - c0 == 2
    assert np.array_equal(np.asarray(final[0]), np.arange(8))


# -- plan / init-dtype resolution ---------------------------------------------


def test_resolve_init_dtype():
    assert transport.resolve_init_dtype(None) is None
    assert transport.resolve_init_dtype("") is None
    assert transport.resolve_init_dtype("bf16") == jnp.bfloat16
    assert transport.resolve_init_dtype("bfloat16") == jnp.bfloat16
    with pytest.raises(ValueError):
        transport.resolve_init_dtype("int8")  # not floating
    with pytest.raises(ValueError):
        transport.resolve_init_dtype("no-such-dtype")


def test_plan_transport_eligibility():
    # f32 param → converts; f32 buffer (mask False) → pass-through;
    # bf16/f16 contracts (equal width) and ints → no plan member.
    plan = transport.plan_transport(
        [jnp.float32, jnp.float32, jnp.bfloat16, jnp.int32],
        [True, False, True, True], jnp.bfloat16,
    )
    assert plan is not None and plan.converts
    assert plan.storage == (jnp.bfloat16, None, None, None)
    # nothing eligible → None (the engines run their default path)
    assert transport.plan_transport(
        [jnp.bfloat16, jnp.int32], [True, True], jnp.bfloat16
    ) is None
    assert transport.plan_transport(
        [jnp.float32], [True], None
    ) is None


# -- the bf16 init fast path --------------------------------------------------


def test_bf16_engines_agree_and_round_exactly(ref, ref_bf16, cache_dir):
    """The fast path's tolerance contract is EXACT: each value is the
    bf16 rounding of the default path's value (upcast back on device),
    and the two engines agree bitwise with each other.  Contract dtypes
    are preserved — f32 params stay f32, the f32 buffer is untouched."""
    import ml_dtypes

    auto = _run("auto", cache_dir, materialize_init_dtype="bf16")
    _assert_bitwise(auto, ref_bf16)
    stats = mat.last_run_stats()
    assert stats["mode"] == "pipelined"
    # The buffer pass-through slot makes donation real on this jax.
    assert stats["bytes_donated"] > 0
    for k, v in auto.items():
        assert v.dtype == ref[k].dtype
        expected = ref[k].astype(ml_dtypes.bfloat16).astype(ref[k].dtype)
        assert np.array_equal(v, expected), k


def test_bf16_exact_when_contract_is_bf16(cache_dir):
    """param_dtype=bf16 under the bf16 transport: contract dtype ==
    init dtype, no upcast exists, and the program is byte-identical to
    the default path's — exact-bitwise by construction."""
    a = _run("auto", cache_dir, param_dtype=jnp.bfloat16,
             materialize_init_dtype="bf16")
    b = _run("auto", cache_dir, param_dtype=jnp.bfloat16)
    _assert_bitwise(a, b)
    assert mat.last_run_stats()["bytes_donated"] == 0


@pytest.mark.slow
def test_chaos_execute_fault_with_donation(ref_bf16, cache_dir):
    """Chaos `execute` faults with donation + bf16 enabled: retries must
    not consume a donated buffer twice — the run survives bitwise-equal
    to the fault-free fast path."""
    vals = _run("auto", cache_dir, materialize_init_dtype="bf16",
                fault_plan="execute@2=raise")
    _assert_bitwise(vals, ref_bf16)


@pytest.mark.slow
def test_bf16_seed_variation(ref_bf16, cache_dir):
    """A different seed through the fast path reuses the same compiled
    programs (the PRNG key is a runtime argument) and still matches the
    rounded default."""
    import ml_dtypes

    base = _run("off", cache_dir, seed=7)
    fast = _run("auto", cache_dir, seed=7, materialize_init_dtype="bf16")
    assert any(not np.array_equal(fast[k], ref_bf16[k]) for k in fast)
    for k in fast:
        expected = base[k].astype(ml_dtypes.bfloat16).astype(base[k].dtype)
        assert np.array_equal(fast[k], expected), k


# -- serve bring-up plumbing --------------------------------------------------


def test_serve_init_fingerprint_salted_by_init_dtype():
    """The serving init program's registry fingerprint must change when
    the transport fast path is armed (the compiled bytes differ), while
    prefill/decode fingerprints stay stable; the init spec carries the
    upcast plan."""
    from torchdistx_tpu.models import PRESETS
    from torchdistx_tpu.serve.programs import ServeConfig, serve_program_specs

    cfg = PRESETS["tiny"]
    scfg = ServeConfig(max_batch=2, page_size=8, n_pages=8,
                       max_pages_per_seq=2, prefill_buckets=(8,))
    default = serve_program_specs("llama", cfg, scfg)
    with tdx_config.override(materialize_init_dtype="bf16"):
        fast = serve_program_specs("llama", cfg, scfg)
    d = {s.name: s for s in default}
    f = {s.name: s for s in fast}
    assert d["init"].tplan is None
    assert f["init"].tplan is not None and f["init"].tplan.converts
    assert d["init"].program_fp != f["init"].program_fp
    assert d["decode"].program_fp == f["decode"].program_fp
    # ShapeDtypeStructs keep the POST-upcast contract dtypes: the
    # lowered decode signature consumes what the upcast delivers.
    for s, st in zip(f["init"].tplan.final, f["init"].tplan.storage):
        if st is not None:
            assert jnp.dtype(s) == jnp.float32


# -- link probe sweep ---------------------------------------------------------


def test_link_probe_sweep(monkeypatch):
    from torchdistx_tpu.observe import costmodel

    monkeypatch.setenv("TDX_LINK_PROBE_MB", "1,2")
    costmodel.reset_link_probe()
    try:
        gbps = costmodel.link_bandwidth_gbps()
        assert gbps and gbps > 0
        assert costmodel.link_probe_size_mb() in (1, 2)
        # cached_only returns the cached sweep result without re-probing
        assert costmodel.link_bandwidth_gbps(cached_only=True) == gbps
    finally:
        costmodel.reset_link_probe()
