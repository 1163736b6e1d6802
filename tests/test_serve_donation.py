"""Every serving program that takes the KV pools (and, for a hybrid
stack, the recurrent state) CONSUMES them and returns them in the same
buffers (ROADMAP S1, PR 33): the compiled program aliases them, a call
deletes the arrays it was given, the values are bitwise those of the
same program compiled without donation, and a call that fails after it
consumed the pools ends in rebuilt pools and recomputed requests."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from torchdistx_tpu import compile_service, observe
from torchdistx_tpu.models import (TINY, TINY_AFMOE, TINY_JAMBA,
                                   TINY_OLMO_HYBRID, TINY_PANGU_ULTRA_MOE)
from torchdistx_tpu.observe.costmodel import program_costs
from torchdistx_tpu.serve import (Request, ServeConfig, ServeEngine,
                                  serve_program_specs)
from torchdistx_tpu.serve.programs import compile_serving_program

SHAPE = dict(max_batch=2, page_size=8, n_pages=16, max_pages_per_seq=4,
             prefill_buckets=(8,), spec_buckets=(2,))
FAMILIES = {
    "llama": (TINY, ServeConfig(**SHAPE)),
    "jamba": (TINY_JAMBA, ServeConfig(**SHAPE, prefix_cache=False,
                                      spec_decode=False)),
    # the window group's pool and the held experts' pair counts behind
    # the full group's two pools
    "afmoe": (TINY_AFMOE, ServeConfig(**SHAPE, prefix_cache=False,
                                      spec_decode=False)),
    # the delta-rule state and the conv tail over q, k and v's channels
    "olmo_hybrid": (TINY_OLMO_HYBRID, ServeConfig(**SHAPE, prefix_cache=False,
                                                  spec_decode=False)),
    # the latent group (its V pool has no page) and the pair counts
    "pangu_ultra_moe": (TINY_PANGU_ULTRA_MOE, ServeConfig(
        **SHAPE, prefix_cache=False, spec_decode=False)),
}
PROGRAMS = {
    "llama": ("prefill-8", "chunk-8", "cow", "decode", "verify-2"),
    "jamba": ("prefill-8", "chunk-8", "decode"),
    "afmoe": ("prefill-8", "chunk-8", "decode"),
    "olmo_hybrid": ("prefill-8", "chunk-8", "decode"),
    "pangu_ultra_moe": ("prefill-8", "chunk-8", "decode"),
}
# What a model program carries: the two pools and the state behind them.
CARRIED = {"llama": 2, "pangu_ultra_moe": 3}
KINDS = [(f, p) for f, ps in PROGRAMS.items() for p in ps]


@pytest.fixture(scope="module")
def built():
    """family -> (specs by name, compiled programs by name, params): one
    trace and one compile a program for the whole file."""
    out = {}
    for family, (cfg, scfg) in FAMILIES.items():
        specs = {s.name: s for s in serve_program_specs(family, cfg, scfg)}
        progs = {n: compile_serving_program(s)[0] for n, s in specs.items()}
        init = specs["init"]
        params = jax.tree.unflatten(init.treedef, list(progs["init"]()))
        out[family] = (specs, progs, params)
    return out


def _engine(family, built, programs=None):
    cfg, scfg = FAMILIES[family]
    specs, progs, params = built[family]
    eng = ServeEngine(family, cfg, params, serve_cfg=scfg)
    eng._programs.update(programs if programs is not None else progs)
    return eng


# -- (a) the record that the mechanism engaged --------------------------------


@pytest.mark.parametrize("family,program", KINDS)
def test_program_consumes_and_aliases_its_pools(built, family, program):
    specs, progs, params = built[family]
    spec = specs[program]
    n = CARRIED.get(family, 4)  # the pools; the state behind them
    first = 0 if program == "cow" else 1
    assert spec.consumes == tuple(range(first, first + n))
    carried = [spec.args[i] for i in spec.consumes]
    assert program_costs(progs[program])["alias_bytes"] >= sum(
        a.size * a.dtype.itemsize for a in carried)
    args = [params if i == 0 and program != "cow"
            else jnp.zeros(a.shape, a.dtype)
            for i, a in enumerate(spec.args)]
    out = progs[program](*args)
    for i, a in enumerate(args):
        if i in spec.consumes:
            # Gone, but still described: what the engine reads of a pool
            # after a call (its shape, dtype and sharding) stays readable.
            assert a.is_deleted()
            assert (a.shape, a.dtype) == (spec.args[i].shape,
                                          spec.args[i].dtype)
        elif i or program == "cow":
            assert not a.is_deleted()
    back = out[-n:]
    assert [(o.shape, o.dtype) for o in back] == [
        (a.shape, a.dtype) for a in carried]
    assert not any(o.is_deleted() for o in back)
    assert not any(l.is_deleted() for l in jax.tree.leaves(params))


@pytest.mark.parametrize("family", list(FAMILIES))
def test_init_consumes_nothing(built, family):
    specs, progs, _ = built[family]
    assert specs["init"].consumes == ()
    assert program_costs(progs["init"]).get("alias_bytes", 0.0) == 0.0


# -- (b) the same values as without donation ----------------------------------


def _requests(tag):
    return [Request(f"{tag}0", [5, 9, 2], max_new_tokens=6),
            Request(f"{tag}1", [17, 3, 3, 8, 1, 101, 7, 7, 7, 40, 2],
                    max_new_tokens=5),  # over the bucket: chunked
            Request(f"{tag}2", [7] * 6, max_new_tokens=7)]


@pytest.mark.parametrize("family", list(FAMILIES))
def test_tokens_and_logits_are_bitwise_those_of_undonated_programs(
        built, family):
    specs, _, _ = built[family]
    # The undonated twin lives here and nowhere in the program.
    plain = {n: jax.jit(s.fn).lower(*s.args).compile()
             for n, s in specs.items() if n != "init"}
    assert all(program_costs(p).get("alias_bytes", 0.0) == 0.0
               for p in plain.values())
    donated, undonated = _engine(family, built), _engine(family, built, plain)
    got = donated.run(_requests("d"))
    want = undonated.run(_requests("d"))
    assert got == want and len(got) == 3
    for rid in got:
        np.testing.assert_array_equal(donated.final_logits[rid],
                                      undonated.final_logits[rid])
    assert set(donated.program_calls) >= set(PROGRAMS[family]) - {
        "cow", "verify-2"}
    for a, b in zip((donated.k_pages, donated.v_pages, *donated.state),
                    (undonated.k_pages, undonated.v_pages, *undonated.state)):
        # Page 0 of every layer is the null page: nothing reads it.
        a, b = np.asarray(a), np.asarray(b)
        if a.ndim == 5:
            a, b = a[:, 1:], b[:, 1:]
        np.testing.assert_array_equal(a, b)


# -- (c) a call that fails after it consumed the pools ------------------------


class _FailsOnce:
    """A compiled program that, on its ``nth`` call, takes its consumed
    arguments as a donated call does and then fails as a device does."""

    def __init__(self, prog, consumes, nth):
        self.prog, self.consumes, self.left = prog, consumes, nth

    def __call__(self, *args):
        self.left -= 1
        if self.left == 0:
            for i in self.consumes:
                args[i].delete()
            raise jax.errors.JaxRuntimeError("INTERNAL: planted device fault")
        return self.prog(*args)


@pytest.mark.parametrize("family,program,nth", [
    ("llama", "decode", 3), ("llama", "prefill-8", 2), ("llama", "chunk-8", 2),
    ("llama", "cow", 1), ("jamba", "decode", 3), ("jamba", "prefill-8", 2),
    ("jamba", "chunk-8", 2), ("afmoe", "decode", 3), ("afmoe", "chunk-8", 2),
    ("olmo_hybrid", "decode", 3), ("olmo_hybrid", "chunk-8", 2),
    ("pangu_ultra_moe", "decode", 3), ("pangu_ultra_moe", "chunk-8", 2)])
def test_fault_inside_a_donated_call_rebuilds_the_pools(built, family,
                                                        program, nth):
    assert issubclass(jax.errors.JaxRuntimeError,
                      compile_service.retryable_errors())
    specs, progs, _ = built[family]
    reqs = _requests("f")
    if program == "cow":
        # A prompt cached whole and page-aligned: the second request's one
        # recomputed position lands in a shared page.
        reqs = [Request("f0", [3] * 8, max_new_tokens=3),
                Request("f1", [3] * 8, max_new_tokens=4, arrival_step=3)]
    want = _engine(family, built).run(
        [Request(r.rid, list(r.tokens), r.max_new_tokens,
                 arrival_step=r.arrival_step) for r in reqs])
    eng = _engine(family, built, {**progs, program: _FailsOnce(
        progs[program], specs[program].consumes, nth)})
    rebuilds = observe.counter("tdx.serve.pool_rebuilds")
    before = rebuilds.value
    for r in reqs:
        eng.submit(r)
    observe.enable(True)
    try:
        while eng._programs[program].left > 0:
            n0 = len(observe.tracer().events)
            eng.step()
    finally:
        observe.enable(None)
    faults = [e["args"] for e in list(observe.tracer().events)[n0:]
              if e["name"] == "serve.fault"]
    assert [f["pools_lost"] for f in faults] == [True]
    # Right after the faulted step: new pools, nothing cached, nothing held.
    assert rebuilds.value == before + 1
    assert not eng._pools_lost()
    assert len(eng.prefix) == 0 and not eng.active
    assert eng.kv.pages_in_use == 0 and eng.kv.state_slots_in_use == 0
    assert len(eng.state) == CARRIED.get(family, 4) - 2
    assert eng.kv.window_pages_in_use == 0
    assert {r.rid for r in eng.waiting} == {
        r.rid for r in reqs} - set(eng.results)
    got = eng.run()
    assert got == want and set(got) == {r.rid for r in reqs}
    assert rebuilds.value == before + 1
