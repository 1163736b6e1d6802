"""Compile a recorded init graph into a JAX function.

This is the TPU-native replacement for the reference's eager boxed replay
(``Op::materialize`` → ``OperatorHandle::callBoxed`` on the real backend,
deferred_init.cc:258-268): instead of replaying op-by-op into host/device
memory, the whole recording is *traced* into a single JAX function, jitted
with ``out_shardings``, and executed by XLA — which partitions the init
computation (including RNG) across the device mesh so each chip computes
and stores only its own shard.  No full parameter ever exists on the host.

Alias semantics (the hard part of the reference's engine, §3.5 of
SURVEY.md) are preserved functionally: every value is a ``Box``; views are
``Box``es with forward/backward lenses onto a base box, so an in-place op
through a view scatters back into the base — e.g. ``Embedding``'s
``weight[padding_idx].fill_(0)`` compiles to ``base.at[idx].set(0)``.
"""

from __future__ import annotations

import hashlib
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import torch

from .. import observe
from .._graph import CONTEXT_KEY, OpNode, get_fake_context
from ..fake import FakeTensor
from ._dtypes import to_numpy
from .ops import TABLE

_STRIP_KWARGS = {"device", "layout", "pin_memory", "memory_format", "generator"}


class Box:
    """A mutable binding for one tensor value during graph interpretation."""

    __slots__ = ("array",)

    def __init__(self, array):
        self.array = array

    def read(self):
        return self.array

    def write(self, value) -> None:
        self.array = value


class ViewBox(Box):
    """A view onto another box: reads through ``fwd``, writes through
    ``bwd`` (scatter into the base)."""

    __slots__ = ("base", "fwd", "bwd")

    def __init__(self, base: Box, fwd: Callable, bwd: Callable):
        self.base = base
        self.fwd = fwd
        self.bwd = bwd

    def read(self):
        return self.fwd(self.base.read())

    def write(self, value) -> None:
        self.base.write(self.bwd(self.base.read(), value))


class TraceContext:
    """Passed to every op impl; provides the per-node RNG key."""

    def __init__(self, base_key):
        self.base_key = base_key
        self._knr = 0
        self.used_rng = False
        # Factory default dtype: from the op's captured thread-local state
        # (a recording made under torch.set_default_dtype resolves factory
        # ops recorded without an explicit dtype= the way torch would).
        self.default_dtype = None
        # The node being interpreted — impls that need its recorded
        # output geometry (aten.resize_) read it here.
        self.node = None

    def set_node(self, node: "OpNode") -> None:
        self._knr = node.key_nr
        self.node = node
        self._set_default_dtype(node)

    def _set_default_dtype(self, node: "OpNode") -> None:
        from ._dtypes import jax_dtype

        tls = getattr(node.op, "tls", None)
        self.default_dtype = (
            jax_dtype(tls.default_dtype) if tls is not None else None
        )

    def key(self):
        self.used_rng = True
        return jax.random.fold_in(self.base_key, self._knr)


class _BatchedTraceContext(TraceContext):
    """TraceContext for one instance of an instance-batched component
    (the ``lax.scan`` body in build_init_fn): the per-node key_nr is a
    traced element of the instance's key_nr vector, so fold_in produces
    bitwise-identical keys to the unbatched interpretation."""

    def __init__(self, base_key, knr_vec, local_index: Dict[int, int]):
        super().__init__(base_key)
        self._knr_vec = knr_vec
        self._local = local_index

    def set_node(self, node: "OpNode") -> None:
        self._knr = self._knr_vec[self._local[id(node)]]
        self.node = node
        self._set_default_dtype(node)


def _op_name(node: OpNode) -> str:
    func = node.op.func
    try:
        return f"{func.namespace}.{func._schema.name.split('::')[-1]}.{func._overloadname or 'default'}"
    except AttributeError:
        return node.op.name


def _dep_box(node, idx, env) -> Box:
    """The box for output ``idx`` of ``node``, creating a constant box if
    the node was materialized early (terminal ops) and never entered the
    interpreted node list (build_call_stack skips materialized deps)."""
    box = env.get((id(node), idx))
    if box is None:
        if node.materialized and node.outputs is not None:
            box = _const_box(node.outputs[idx], env)
            env[(id(node), idx)] = box
        else:
            raise KeyError(
                f"dependency `{node.op.name}` (op #{node.op_nr}) was not "
                f"interpreted before its dependent"
            )
    return box


# Early-materialized nodes enter the JAX program as constants — but their
# cached torch outputs can ALIAS each other (a value read materializes a
# whole view chain, and later *recorded* in-place ops may write through any
# of its members).  Independent constant boxes would break that coupling:
# the write lands in one box and every other alias keeps the stale value.
# So constants sharing a torch storage share ONE flat root box, and each
# cached output becomes a ViewBox whose lens is rebuilt from its torch
# geometry (size/stride/storage_offset) — the functional equivalent of the
# reference replaying in-place ops against real aliasing tensors.
_ROOTS_KEY = "_tdx_const_roots"


def _storage_key(t: torch.Tensor):
    s = t.untyped_storage()
    return (s.data_ptr(), s.nbytes())


def _view_lens(t: torch.Tensor):
    """(fwd, bwd) index lenses mapping a flat storage array to the logical
    value of ``t`` and back, from its torch geometry.

    The common case — a contiguous tensor spanning its whole storage —
    is a free reshape; anything strided uses the shared flat strided
    lens (ops.strided_lens, same code path as aten.as_strided)."""
    size = tuple(t.shape)
    if (
        t.storage_offset() == 0
        and t.is_contiguous()
        and t.numel() * t.element_size() == t.untyped_storage().nbytes()
    ):
        return (lambda flat: flat.reshape(size),
                lambda flat, value: value.reshape(flat.shape))

    from .ops import strided_lens

    return strided_lens(size, t.stride(), t.storage_offset())


def _const_box(out: torch.Tensor, env) -> Box:
    """A box for one early-materialized constant, alias-linked through a
    shared per-storage root so recorded in-place ops through any cached
    view stay visible to every other alias."""
    s = out.untyped_storage()
    if s.nbytes() == 0 or s.nbytes() % out.element_size() != 0:
        return Box(jnp.asarray(to_numpy(out)))
    roots = env.setdefault(_ROOTS_KEY, {})
    key = _storage_key(out)
    entry = roots.get(key)
    if entry is None:
        flat = torch.empty(0, dtype=out.dtype)
        flat.set_(s)  # 1-D tensor spanning the whole storage
        entry = (out.dtype, Box(jnp.asarray(to_numpy(flat))))
        roots[key] = entry
    root_dtype, root_box = entry
    if out.dtype != root_dtype:
        # Mixed-dtype views of one storage (e.g. view_as_real of a complex
        # base): no lens over the typed root, and an UNLINKED constant
        # would silently reintroduce the stale-alias bug — refuse, like
        # every other unsupported construct in the bridge.
        raise NotImplementedError(
            f"early-materialized constants alias one storage with mixed "
            f"dtypes ({root_dtype} vs {out.dtype}); the JAX bridge cannot "
            f"alias-link them. Materialize these tensors with the eager "
            f"torch ReplayTarget instead."
        )
    fwd, bwd = _view_lens(out)
    return ViewBox(root_box, fwd, bwd)


def _resolve_value(obj, env, deps):
    """Resolve a preserved-stack entry to a python/jnp value (reads through
    boxes)."""
    from .._graph import _Dep

    if isinstance(obj, _Dep):
        node, idx = deps[obj.index]
        return _dep_box(node, idx, env).read()
    if isinstance(obj, torch.Tensor):
        return jnp.asarray(to_numpy(obj))
    if isinstance(obj, (list, tuple)):
        r = [_resolve_value(x, env, deps) for x in obj]
        return r if isinstance(obj, list) else tuple(r)
    if isinstance(obj, dict):
        return {k: _resolve_value(v, env, deps) for k, v in obj.items()}
    return obj


def _first_dep_box(args, env, deps):
    from .._graph import _Dep

    for a in args:
        if isinstance(a, _Dep):
            node, idx = deps[a.index]
            return _dep_box(node, idx, env)
    raise NotImplementedError("in-place/view op with no tensor input")


def _c_contiguous(geom) -> bool:
    """Whether (size, stride, offset, storage_numel) is a C-contiguous
    layout spanning its whole storage — the case where a box's logical
    value IS its storage order.  Shares the producer's predicate so the
    record-time omission rule and this consumer test cannot drift."""
    from .._graph import geom_is_c_contig_spanning

    return geom_is_c_contig_spanning(*geom)


def _live_root_geom(node):
    """Physical geometry of the ROOT BOX owner reached from ``node``'s
    first tensor dependency, mirroring the Box alias chain exactly
    (views and in-place ops reuse their base's box; set_data aliases its
    rhs).  None when the root is materialized (alias-linked constant
    roots are already storage-ordered) or unknown."""
    from .._graph import _Dep

    def first_dep(n):
        d = next((a for a in n.op.args if isinstance(a, _Dep)), None)
        return None if d is None else n.dependencies[d.index]

    cur = first_dep(node)
    while cur is not None:
        n, idx = cur
        if n.materialized:
            return None
        name = _op_name(n)
        if name == "tdx::set_data":
            rhs = n.op.args[1]
            cur = n.dependencies[rhs.index] if isinstance(rhs, _Dep) else None
            continue
        entry = TABLE.get(name)
        if entry is None:
            return None
        kind = entry[0]
        if kind in ("view", "multiview", "inplace"):
            cur = first_dep(n)
            continue
        if kind == "out":
            out_kw = n.op.kwargs.get("out")
            if isinstance(out_kw, _Dep):
                cur = n.dependencies[out_kw.index]
                continue
            last = None
            for a in n.op.args:
                if isinstance(a, _Dep):
                    last = a
            cur = n.dependencies[last.index] if last is not None else None
            continue
        return n.out_geom.get(idx)  # pure: this node owns the root box
    return None


def _split_out_arg(args, env, deps):
    """For out-variant ops (``aten.eye.m_out``): the written tensor is the
    LAST tensor argument.  Returns (out_box, args_without_out)."""
    from .._graph import _Dep

    last = None
    for i, a in enumerate(args):
        if isinstance(a, _Dep):
            last = i
    if last is None:
        raise NotImplementedError("out-variant op with no tensor argument")
    node, idx = deps[args[last].index]
    return _dep_box(node, idx, env), args[:last] + args[last + 1:]


def interpret_node(node: OpNode, env: Dict, ctx: TraceContext) -> None:
    """Evaluate one node into ``env``, keyed by ``(id(node), tensor_idx)``."""
    if node.materialized and node.outputs is not None:
        # Terminal ops (aten::item) force early torch materialization during
        # recording (deferred_init.cc:792-797); their results enter the JAX
        # program as constants (alias-linked — see _const_box).
        for i, out in enumerate(node.outputs):
            if isinstance(out, torch.Tensor):
                env.setdefault((id(node), i), _const_box(out, env))
        return

    name = _op_name(node)
    if name == "tdx::set_data":
        # `base.data = value` rebinds base's storage to value's: alias the
        # BOXES, not just the value — later mutations through either side
        # must be visible through the other (torch replay gets this from
        # real set_data; the box env needs it made explicit).
        from .._graph import _Dep

        rhs = node.op.args[1]
        if isinstance(rhs, _Dep):
            dep, idx = node.dependencies[rhs.index]
            env[(id(node), 0)] = _dep_box(dep, idx, env)
        else:
            # Constant (real-tensor) rhs: through _const_box so a
            # non-contiguous rhs gets a storage-ordered root + geometry
            # lens — a logical-order Box would scramble storage-relative
            # as_strided gathers over it (review repro: p.data = real.t()
            # then deepcopy).
            env[(id(node), 0)] = _const_box(rhs, env)
        return

    entry = TABLE.get(name)
    if entry is None:
        raise NotImplementedError(
            f"`{name}` (recorded at op #{node.op_nr}) has no JAX lowering in "
            f"torchdistx_tpu.jax_bridge.ops. Either add one to the table or "
            f"materialize this tensor with the eager torch ReplayTarget "
            f"(torchdistx_tpu.deferred_init.materialize_module) instead."
        )
    kind, impl = entry

    # key_nr, not op_nr: RNG keys must be session-relative so the same
    # recording yields the same parameters regardless of what else the
    # process recorded before (see _graph.begin_recording_session).
    ctx.set_node(node)
    args = node.op.args
    kwargs = {k: v for k, v in node.op.kwargs.items() if k not in _STRIP_KWARGS and v is not None}
    # Positional device/generator-like leaves are stripped by type.
    args = tuple(a for a in args if not isinstance(a, (torch.device, torch.Generator)))

    if kind == "pure":
        vals = [_resolve_value(a, env, node.dependencies) for a in args]
        kw = {k: _resolve_value(v, env, node.dependencies) for k, v in kwargs.items()}
        out = impl(ctx, *vals, **kw)
        outs = out if isinstance(out, (list, tuple)) else (out,)
        for i, o in enumerate(outs):
            env[(id(node), i)] = Box(o)
    elif kind in ("inplace", "out"):
        if kind == "inplace":
            box = _first_dep_box(args, env, node.dependencies)
            rest_args = args[1:]
        else:
            # out-variant: compute from the non-out args, write into the
            # out tensor's box (the op's output aliases it).  `out` is
            # usually a kwarg (torch.eye(n, out=t)); positional fallback.
            from .._graph import _Dep

            out_kw = node.op.kwargs.get("out")
            if isinstance(out_kw, _Dep):
                dep, di = node.dependencies[out_kw.index]
                box = _dep_box(dep, di, env)
                rest_args = args
            else:
                box, rest_args = _split_out_arg(args, env, node.dependencies)
        rest = [_resolve_value(a, env, node.dependencies) for a in rest_args]
        kw = {
            k: _resolve_value(v, env, node.dependencies)
            for k, v in kwargs.items()
            if k != "out"
        }
        new = impl(ctx, box.read(), *rest, **kw)
        box.write(new)
        env[(id(node), 0)] = box
    elif kind == "view":
        box = _first_dep_box(args, env, node.dependencies)
        if name in ("aten.as_strided.default", "aten.resize_.default"):
            # as_strided and resize_ are STORAGE-relative, not
            # view-relative: resolve to the root box.  A factory root's
            # logical value spans the storage contiguously; an OP-OUTPUT
            # root can be dense but permuted (torch preserves input
            # striding), in which case a storage-order adapter scatters
            # the logical value into physical order first (soak seed
            # 765331).
            while isinstance(box, ViewBox):
                box = box.base
            geom = _live_root_geom(node)
            if name == "aten.resize_.default":
                # A growing resize_ reads storage the root box does not
                # cover (fresh elements are uninitialized garbage in
                # eager torch anyway) — no JAX lowering.
                capacity = geom[3] if geom is not None else int(box.read().size)
                og = node.out_geom.get(0)
                top = (
                    og[2] + int(np.prod(og[0])) if og is not None
                    else int(np.prod([int(s) for s in node.op.args[1]]))
                )
                if top > capacity:
                    raise NotImplementedError(
                        f"aten.resize_ grows the storage ({top} > "
                        f"{capacity} elements; the new tail is "
                        f"uninitialized) — materialize this tensor with "
                        f"the eager torch ReplayTarget instead."
                    )
            if geom is not None and not _c_contiguous(geom):
                from .ops import strided_lens

                size, stride, offset, snumel = geom
                sfwd, sbwd = strided_lens(size, stride, offset)

                def to_storage(logical, _sbwd=sbwd, _n=snumel):
                    return _sbwd(
                        jnp.zeros((_n,), dtype=logical.dtype), logical
                    )

                box = ViewBox(box, to_storage, lambda _l, flat, _sfwd=sfwd: _sfwd(flat))
        rest = [_resolve_value(a, env, node.dependencies) for a in args[1:]]
        kw = {k: _resolve_value(v, env, node.dependencies) for k, v in kwargs.items()}
        base_shape = tuple(box.read().shape)
        fwd, bwd = impl(ctx, base_shape, *rest, **kw)
        env[(id(node), 0)] = ViewBox(box, fwd, bwd)
    elif kind == "multiview":
        # One node, several aliasing view outputs (aten.split):
        # each output gets its own lens over the shared base box.
        box = _first_dep_box(args, env, node.dependencies)
        rest = [_resolve_value(a, env, node.dependencies) for a in args[1:]]
        kw = {k: _resolve_value(v, env, node.dependencies) for k, v in kwargs.items()}
        base_shape = tuple(box.read().shape)
        for i, (fwd, bwd) in enumerate(impl(ctx, base_shape, *rest, **kw)):
            env[(id(node), i)] = ViewBox(box, fwd, bwd)
    else:  # pragma: no cover
        raise AssertionError(kind)


def collect_nodes(fakes: Sequence[FakeTensor]) -> List[OpNode]:
    """Union of the fakes' call stacks in chronological order."""
    nodes: List[OpNode] = []
    seen: set = set()
    for f in fakes:
        ctx = get_fake_context(f, CONTEXT_KEY)
        if ctx is None:
            raise ValueError(
                "A tensor passed to the JAX materializer has no deferred-init "
                "recording (it is either real or already materialized)."
            )
        for n in ctx.node.build_call_stack():
            if id(n) not in seen:
                seen.add(id(n))
                nodes.append(n)
    nodes.sort(key=lambda n: n.op_nr)
    return nodes


# ---------------------------------------------------------------------------
# Isomorphic-component batching
#
# A model's recorded init graph is a forest of per-parameter op chains, and
# a deep model records the *same* chain once per layer (80 structurally
# identical `empty → normal_` chains for an 80-layer model).  Tracing and
# compiling each chain separately makes XLA compile time O(depth) — the
# round-1 bench spent 5.4 s of a 5.7 s run inside the compiler.  Instead we:
#
#   1. split the node list into dependency-connected components;
#   2. fingerprint each component's structure (op names, args/kwargs with
#      dependency edges rewritten to component-local indices, constant
#      tensors by value hash) — everything EXCEPT the per-node RNG key_nr;
#   3. interpret one representative per fingerprint and run it once per
#      instance with ``lax.scan`` over the stacked key_nr vectors.
#
# Compile cost becomes O(unique structures); RNG results are bitwise
# identical to the unbatched interpretation because each scan iteration
# IS the per-instance computation (same fold_in key, same draw).
# ---------------------------------------------------------------------------


def _components(nodes: Sequence[OpNode]) -> List[List[OpNode]]:
    """Dependency-connected components, each sorted chronologically,
    ordered by first op.  ``nodes`` must be dependency-closed (it is: it
    comes from build_call_stack unions)."""
    parent = {id(n): id(n) for n in nodes}

    def find(x: int) -> int:
        while parent[x] != x:
            parent[x] = parent[parent[x]]
            x = parent[x]
        return x

    def union(a: int, b: int) -> None:
        ra, rb = find(a), find(b)
        if ra != rb:
            parent[ra] = rb

    # Components touching the same early-materialized STORAGE must stay
    # together: their constants alias through one shared root box (see
    # _const_box), so a recorded in-place write in one component is visible
    # to readers in the other — chronological interleaving (and never
    # batching them apart) is required for correctness.
    storage_anchor: Dict[Any, int] = {}

    def union_storage(nid: int, out) -> None:
        if not isinstance(out, torch.Tensor) or out.untyped_storage().nbytes() == 0:
            return
        key = _storage_key(out)
        a = storage_anchor.setdefault(key, nid)
        union(nid, a)

    for n in nodes:
        if n.materialized and n.outputs is not None:
            for out in n.outputs:
                union_storage(id(n), out)
        for d, idx in n.dependencies:
            if id(d) in parent:
                union(id(n), id(d))
            elif d.materialized and d.outputs is not None and idx < len(d.outputs):
                union_storage(id(n), d.outputs[idx])
    comps: Dict[int, List[OpNode]] = {}
    for n in nodes:  # already in op_nr order
        comps.setdefault(find(id(n)), []).append(n)
    return list(comps.values())


def _value_sig(obj, deps, local_index):
    from .._graph import _Dep

    if isinstance(obj, _Dep):
        node, idx = deps[obj.index]
        li = local_index.get(id(node))
        if li is None:
            # Dependency outside the component (materialized early by a
            # terminal op): its value is instance-specific, so make the
            # signature unique — the component stays unbatched.
            return ("extdep", id(node), idx)
        return ("dep", li, idx)
    if isinstance(obj, torch.Tensor):
        arr = to_numpy(obj)
        return ("tensor", arr.shape, str(arr.dtype), hashlib.sha1(arr.tobytes()).hexdigest())
    if isinstance(obj, (list, tuple)):
        kind = "list" if isinstance(obj, list) else "tuple"
        return (kind, tuple(_value_sig(x, deps, local_index) for x in obj))
    if isinstance(obj, dict):
        return ("dict", tuple(sorted((k, _value_sig(v, deps, local_index)) for k, v in obj.items())))
    if isinstance(obj, torch.Size):
        return ("size", tuple(obj))
    if isinstance(obj, (torch.device, torch.dtype, torch.layout, torch.memory_format)):
        return ("torch", str(obj))
    return ("py", type(obj).__name__, repr(obj))


def _node_sig(node: OpNode, local_index: Dict[int, int]):
    if node.materialized:
        # Early-materialized values are instance-specific constants.
        return ("terminal", id(node))
    tls = node.op.tls
    return (
        _op_name(node),
        _value_sig(node.op.args, node.dependencies, local_index),
        _value_sig(node.op.kwargs, node.dependencies, local_index),
        # Replay-relevant TLS is part of the structure: two chains recorded
        # under different default dtypes must not batch together.
        str(tls.default_dtype),
    )


def _tensor_digest(t: torch.Tensor) -> Tuple:
    arr = to_numpy(t)
    return ("tensor", arr.shape, str(arr.dtype),
            hashlib.sha1(arr.tobytes()).hexdigest())


def _fp_value_sig(obj, deps, local_index):
    """Like :func:`_value_sig` but stable ACROSS PROCESSES: a dependency
    on an early-materialized node outside the local index is signed by
    its cached output *content*, never by ``id()`` — the resume-manifest
    fingerprint must mean the same thing in the rerun that consumes it
    as in the interrupted run that wrote it."""
    from .._graph import _Dep

    if isinstance(obj, _Dep):
        node, idx = deps[obj.index]
        li = local_index.get(id(node))
        if li is not None:
            return ("dep", li, idx)
        if node.materialized and node.outputs is not None and idx < len(node.outputs):
            out = node.outputs[idx]
            if isinstance(out, torch.Tensor):
                return ("extconst",) + _tensor_digest(out)
            return ("extconst", "py", repr(out))
        # A live dependency outside the group cannot happen (collect_nodes
        # unions dependency-closed chains); refuse rather than sign with
        # an id() that another process could coincidentally reproduce.
        raise ValueError(
            f"group fingerprint: unstable external dependency on "
            f"{node.op.name!r}"
        )
    if isinstance(obj, torch.Tensor):
        return _tensor_digest(obj)
    if isinstance(obj, (list, tuple)):
        kind = "list" if isinstance(obj, list) else "tuple"
        return (kind, tuple(_fp_value_sig(x, deps, local_index) for x in obj))
    if isinstance(obj, dict):
        return ("dict", tuple(sorted(
            (k, _fp_value_sig(v, deps, local_index)) for k, v in obj.items()
        )))
    return _value_sig(obj, deps, local_index)


def group_fingerprint(fakes: Sequence[FakeTensor]) -> str:
    """Content fingerprint of the recorded init computation of ``fakes``:
    op names, argument values, RNG ``key_nr``s, early-materialized
    constants (by value), and the requested output slots.

    Unlike :func:`_node_sig` (which deliberately excludes ``key_nr`` so
    structurally identical chains batch together), this digest pins the
    exact VALUES the group will produce for a given seed, and it is
    stable across processes — the self-healing materializer keys its
    partial-progress manifest on it, so a rerun only skips a group whose
    recorded computation is identical to the one whose outputs were
    committed (docs/robustness.md)."""
    nodes = collect_nodes(fakes)
    local_index = {id(n): j for j, n in enumerate(nodes)}
    h = hashlib.sha1(b"tdx-group-fp-v1")
    for n in nodes:
        if n.materialized and n.outputs is not None:
            sig: Tuple = ("terminal", tuple(
                _tensor_digest(o) if isinstance(o, torch.Tensor)
                else ("py", repr(o))
                for o in n.outputs
            ))
        else:
            tls = n.op.tls
            sig = (
                _op_name(n),
                _fp_value_sig(n.op.args, n.dependencies, local_index),
                _fp_value_sig(n.op.kwargs, n.dependencies, local_index),
                str(tls.default_dtype) if tls is not None else None,
            )
        h.update(repr((n.key_nr, sig)).encode())
    for f in fakes:
        ctx = get_fake_context(f, CONTEXT_KEY)
        h.update(repr((
            local_index.get(id(ctx.node), -1), ctx.output_index,
            tuple(f.shape), str(f.dtype),
        )).encode())
    return h.hexdigest()


def _group_uses_rng(rep: List[OpNode], need: List[Tuple[int, int]]) -> bool:
    """Abstractly interpret a representative component (jax.eval_shape — no
    FLOPs, no compile) and report whether any op drew from the RNG.  A
    component that never touches the RNG computes the same value for every
    instance, so it is interpreted once and shared instead of scanned."""

    def probe(key):
        lctx = TraceContext(key)
        lenv: Dict = {}
        for n in rep:
            interpret_node(n, lenv, lctx)
        probe.used_rng = lctx.used_rng
        return tuple(lenv[(id(rep[li]), oi)].read() for li, oi in need)

    probe.used_rng = True
    try:
        jax.eval_shape(probe, jax.ShapeDtypeStruct((2,), jnp.uint32))
    except Exception:
        return True  # when in doubt, scan — always correct
    return probe.used_rng


# ---------------------------------------------------------------------------
# Per-group program splitting (the pipelined materialization engine's unit)
#
# The monolithic path traces EVERY component into one XLA program, so a model
# whose layers defeat instance batching (distinct shapes per layer — pyramid
# widths, heterogeneous stacks) compiles one giant module, and XLA compile
# time is superlinear in module size.  Splitting along the same structural
# fingerprint groups the batching machinery already computes yields
# independently jittable sub-programs that (a) compile in sum cheaper than
# the monolith at scale and (b) can be lowered/compiled concurrently and
# executed as each executable lands (materialize._run_init_pipelined).
# Correctness needs no inter-program protocol: components are
# dependency-closed (storage-aliased constants are unioned into one
# component by _components), and per-op fold_in RNG keys make every value
# independent of which program computes it — bitwise-identical either way.
# ---------------------------------------------------------------------------


def split_init_groups(
    fakes: Sequence[FakeTensor], max_programs: int = 8,
    *, nodes: Optional[List[OpNode]] = None
) -> List[List[int]]:
    """Partition the indices of ``fakes`` into at most ``max_programs``
    bins of structurally related components, each bin an independently
    jittable sub-program (feed ``[fakes[i] for i in bin]`` to
    :func:`build_init_fn`).

    Components are grouped by structural fingerprint first (so instance
    batching inside each sub-program stays as effective as in the
    monolith), then fingerprint groups are greedily cost-balanced into
    bins — compile cost scales with unique structure size, so the cost
    proxy is the representative's node count plus a small per-instance
    term.  Deterministic for a given recording and ``max_programs``:
    ``tools/warm_cache.py`` relies on replaying the exact program set a
    later materialize will request, possibly on a different host.

    ``nodes`` may pass a precollected ``collect_nodes(fakes)`` result so
    callers that already walked the graph don't walk it twice.
    """
    if nodes is None:
        nodes = collect_nodes(fakes)
    comps = _components(nodes)
    node2comp: Dict[int, int] = {}
    for ci, comp in enumerate(comps):
        for n in comp:
            node2comp[id(n)] = ci

    sig2group: Dict[Any, int] = {}
    comp2group: Dict[int, int] = {}
    group_cost: List[int] = []
    for ci, comp in enumerate(comps):
        local_index = {id(n): j for j, n in enumerate(comp)}
        sig = tuple(_node_sig(n, local_index) for n in comp)
        gi = sig2group.get(sig)
        if gi is None:
            gi = sig2group[sig] = len(group_cost)
            group_cost.append(16 * len(comp))  # unique structure: compile cost
        else:
            group_cost[gi] += 1  # repeat instance: scan-iteration cost only
        comp2group[ci] = gi

    group_slots: Dict[int, List[int]] = {}
    for i, f in enumerate(fakes):
        ctx = get_fake_context(f, CONTEXT_KEY)
        gi = comp2group[node2comp[id(ctx.node)]]
        group_slots.setdefault(gi, []).append(i)

    # Greedy cost-balanced bin-pack of the slot-owning groups (groups no
    # requested output reads contribute nothing and are dropped, exactly
    # as build_init_fn skips them).  Largest first, stable tiebreak.
    order = sorted(group_slots, key=lambda g: (-group_cost[g], g))
    n_bins = max(1, min(len(order), max_programs))
    bins: List[List[int]] = [[] for _ in range(n_bins)]
    bin_cost = [0] * n_bins
    for g in order:
        j = bin_cost.index(min(bin_cost))
        bins[j].extend(group_slots[g])
        bin_cost[j] += group_cost[g]
    out = [sorted(b) for b in bins if b]
    out.sort(key=lambda b: b[0])  # deterministic program order
    return out


def build_init_fn(
    fakes: Sequence[FakeTensor], *, dedup: bool = True
) -> Callable[..., Tuple[jax.Array, ...]]:
    """Build ``init_fn(base_key) -> tuple[jax.Array, ...]`` computing the
    values of ``fakes`` from a PRNG key.

    The function is pure and jittable; pass it to ``jax.jit`` with
    ``out_shardings`` to materialize directly into sharded device memory.
    Taking the key as an *argument* (not a baked-in constant) keeps the
    compiled executable reusable across seeds.

    With ``dedup`` (default) structurally identical per-layer init chains
    are interpreted once: RNG-free components are computed a single time
    and shared across instances, RNG-bearing ones run under ``lax.scan``
    over their per-instance key numbers.  Trace+compile cost becomes
    O(unique structures) instead of O(depth); results are bitwise
    identical either way.
    """
    with observe.span(
        "bridge.build_init_fn", category="jax", n_outputs=len(fakes)
    ) as _sp:
        return _build_init_fn(fakes, dedup=dedup, _sp=_sp)


def _build_init_fn(fakes, *, dedup, _sp):
    nodes = collect_nodes(fakes)
    _sp.set(n_nodes=len(nodes), dedup=dedup)
    slots = []
    for f in fakes:
        c = get_fake_context(f, CONTEXT_KEY)
        slots.append((c.node, c.output_index))

    if not dedup:
        def init_fn_flat(base_key):
            env: Dict = {}
            tctx = TraceContext(base_key)
            for n in nodes:
                interpret_node(n, env, tctx)
            return tuple(env[(id(node), idx)].read() for node, idx in slots)

        return init_fn_flat

    # -- group components by structural fingerprint -----------------------
    groups: Dict[Any, List[List[OpNode]]] = {}
    group_order: List[Any] = []
    for comp in _components(nodes):
        local_index = {id(n): j for j, n in enumerate(comp)}
        sig = tuple(_node_sig(n, local_index) for n in comp)
        if sig not in groups:
            groups[sig] = []
            group_order.append(sig)
        groups[sig].append(comp)

    node_loc: Dict[int, Tuple[Any, int, int]] = {}
    for sig, insts in groups.items():
        for inst, comp in enumerate(insts):
            for li, n in enumerate(comp):
                node_loc[id(n)] = (sig, inst, li)

    # Requested outputs per batched group: union over instances of the
    # component-local (node, output) slots that must be returned.
    needed: Dict[Any, List[Tuple[int, int]]] = {}
    for node, oi in slots:
        sig, _inst, li = node_loc[id(node)]
        if len(groups[sig]) > 1:
            lst = needed.setdefault(sig, [])
            if (li, oi) not in lst:
                lst.append((li, oi))

    # Build-time RNG probe per batched group (cheap abstract eval).
    group_rng: Dict[Any, bool] = {}
    for sig in group_order:
        insts = groups[sig]
        need = needed.get(sig)
        if len(insts) > 1 and need:
            group_rng[sig] = _group_uses_rng(insts[0], need)

    # RNG-bearing batched groups with the SAME instance count are merged
    # into ONE lax.scan whose body runs every group's representative for
    # instance i (per-program compile overhead on TPU is ~0.4 s, so one
    # scan for all twelve per-layer chains beats one scan per chain).
    scan_buckets: Dict[int, List[Any]] = {}
    for sig in group_order:
        insts = groups[sig]
        if len(insts) > 1 and needed.get(sig) and group_rng[sig]:
            scan_buckets.setdefault(len(insts), []).append(sig)

    if observe.enabled():  # aggregation itself is O(groups); skip when off
        _sp.set(
            n_components=sum(len(g) for g in groups.values()),
            n_unique_structures=len(groups),
            n_batched_groups=sum(
                1 for sig in group_order
                if len(groups[sig]) > 1 and needed.get(sig)
            ),
        )

    def _interp_rep(sig, knr_vec, base_key):
        """Interpret the representative of ``sig`` with instance key
        numbers ``knr_vec``; return its needed outputs."""
        rep = groups[sig][0]
        lctx = _BatchedTraceContext(
            base_key, knr_vec, {id(n): j for j, n in enumerate(rep)}
        )
        lenv: Dict = {}
        for n in rep:
            interpret_node(n, lenv, lctx)
        return tuple(lenv[(id(rep[li]), oi)].read() for li, oi in needed[sig])

    def init_fn(base_key):
        env: Dict = {}
        # sig -> ("stacked"|"shared", {(li, oi): value}); "stacked" values
        # carry a leading instance dim, "shared" are RNG-free singles.
        gout: Dict[Any, Tuple[str, Dict[Tuple[int, int], jax.Array]]] = {}
        tctx = TraceContext(base_key)
        for sig in group_order:
            insts = groups[sig]
            if len(insts) == 1:
                for n in insts[0]:
                    interpret_node(n, env, tctx)
                continue
            need = needed.get(sig)
            if not need:  # no requested output reads this group
                continue
            if not group_rng[sig]:
                # RNG-free: every instance computes the same value — emit
                # the computation once and share it (e.g. 12 identical
                # causal-mask buffers become one tril).
                rep = insts[0]
                knr_vec = jnp.asarray([n.key_nr for n in rep], dtype=jnp.uint32)
                outs = _interp_rep(sig, knr_vec, base_key)
                gout[sig] = ("shared", dict(zip(need, outs)))

        for k, sigs_k in scan_buckets.items():
            # Stacked key numbers: [k, sum of group node counts].
            segs = []
            off = 0
            mats = []
            for sig in sigs_k:
                insts = groups[sig]
                n = len(insts[0])
                mats.append([[nd.key_nr for nd in comp] for comp in insts])
                segs.append((sig, off, n))
                off += n
            knrs = jnp.concatenate(
                [jnp.asarray(m, dtype=jnp.uint32) for m in mats], axis=1
            )

            def body(c, kv, _segs=tuple(segs)):
                outs = tuple(
                    _interp_rep(sig, kv[o:o + n], base_key)
                    for sig, o, n in _segs
                )
                return c, outs

            # lax.scan, not vmap: the body compiles ONCE with unbatched
            # threefry (vmapped threefry HLO compiles ~7x slower on TPU
            # for matrix-sized draws), and scan iterations are exactly the
            # per-instance calls, so results stay bitwise identical.
            _, allouts = jax.lax.scan(body, None, knrs)
            for (sig, _o, _n), outs in zip(segs, allouts):
                gout[sig] = ("stacked", dict(zip(needed[sig], outs)))

        result = []
        for node, oi in slots:
            sig, inst, li = node_loc[id(node)]
            if len(groups[sig]) > 1:
                kind, vals = gout[sig]
                v = vals[(li, oi)]
                result.append(v[inst] if kind == "stacked" else v)
            else:
                result.append(env[(id(node), oi)].read())
        return tuple(result)

    return init_fn
