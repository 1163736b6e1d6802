"""JAX-native fake arrays and deferred initialization.

The torch frontend reproduces the reference's *mechanism* (dispatch
interposition + replay graph, fake.cc / deferred_init.cc).  For JAX
programs the same two capabilities are idiomatic one-liners in disguise:

* **fake tensors** — abstract evaluation: ``jax.eval_shape`` runs any init
  function with zero FLOPs and zero allocation, yielding full metadata
  (the counterpart of meta-backend shape inference, fake.cc:552-565);
* **the replay graph** — the init *closure itself*: JAX init functions are
  pure, so instead of recording ops imperatively we capture the function
  and its arguments; "materialization" is jitting that closure with
  ``out_shardings`` so XLA computes each parameter's shard in place.

Partial materialization (the reference's ``materialize_tensor`` /
``check_fn`` surface, deferred_init.py:39-87) falls out of XLA dead-code
elimination: materializing one leaf compiles a pruned program that
computes only that leaf's ancestors.

Works with any pytree-returning init — ``flax.linen.Module.init``,
haiku ``transform().init``, or hand-written factories.
"""

from __future__ import annotations

import functools
from typing import Any, Callable, Dict, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from . import transport
from .observe import compilelog
from .parallel.sharding import ShardingPlan

__all__ = [
    "DeferredArray",
    "deferred_init",
    "is_fake",
    "materialize",
    "materialize_leaf",
]


class _Thunk:
    """The captured init closure: the JAX-native replay recording."""

    __slots__ = (
        "fn", "args", "kwargs", "out_treedef", "n_leaves", "paths",
        "_has_params",
    )

    def __init__(self, fn, args, kwargs, out_treedef, n_leaves, paths=()):
        self.fn = fn
        self.args = args
        self.kwargs = kwargs
        self.out_treedef = out_treedef
        self.n_leaves = n_leaves
        # Leaf paths of the FULL recording: param_dtype's params-collection
        # policy must be judged against the whole tree, not whatever
        # subtree a materialize() call happens to pass.
        self.paths = tuple(paths)
        self._has_params = any(
            p.split(".", 1)[0] == "params" for p in self.paths
        )

    def has_params_collection(self) -> bool:
        return self._has_params

    def leaves_fn(self) -> Callable[[], Tuple[jax.Array, ...]]:
        def run():
            out = self.fn(*self.args, **self.kwargs)
            return tuple(jax.tree.leaves(out))

        return run


class DeferredArray:
    """A fake array: full metadata, no storage, plus its recording.

    Counterpart of ``FakeTensorImpl`` (fake.cc:120-347) for the JAX
    frontend; ``shape``/``dtype`` come from abstract evaluation, the
    ``_thunk``/``_leaf_idx`` pair plays the role of the fake-context
    ``DeferredInitContext`` (deferred_init.cc:120-151).
    """

    __slots__ = ("shape", "dtype", "_thunk", "_leaf_idx", "path")

    def __init__(self, aval: jax.ShapeDtypeStruct, thunk: _Thunk, leaf_idx: int, path: str):
        self.shape = tuple(aval.shape)
        self.dtype = aval.dtype
        self._thunk = thunk
        self._leaf_idx = leaf_idx
        self.path = path

    @property
    def size(self) -> int:
        n = 1
        for s in self.shape:
            n *= s
        return n

    @property
    def ndim(self) -> int:
        return len(self.shape)

    def __repr__(self) -> str:
        return (
            f"DeferredArray(shape={self.shape}, dtype={self.dtype.name}, "
            f"path='{self.path}', fake=True)"
        )

    def __array__(self, *a, **kw):
        raise RuntimeError(
            "A DeferredArray has no storage; materialize it first "
            "(torchdistx_tpu.abstract.materialize)."
        )

    def __jax_array__(self):
        raise RuntimeError(
            "A DeferredArray has no storage; materialize it first "
            "(torchdistx_tpu.abstract.materialize)."
        )


def is_fake(x: Any) -> bool:
    return isinstance(x, DeferredArray)


def deferred_init(init_fn: Callable, *args: Any, **kwargs: Any):
    """Run ``init_fn`` abstractly; return its pytree with every array leaf
    replaced by a :class:`DeferredArray`.

    Example (flax)::

        model = LlamaModel(config)
        params = deferred_init(model.init, jax.random.PRNGKey(0), sample_batch)
        # params: pytree of DeferredArray — zero bytes allocated
        real = materialize(params, mesh=mesh, plan=plan)
    """
    # The first step of the jax-native chain, which never loads the compile
    # service: from here on what is traced, lowered and compiled is logged.
    compilelog.install()
    out = jax.eval_shape(init_fn, *args, **kwargs)
    leaves, treedef = jax.tree.flatten(out)
    paths_leaves = jax.tree_util.tree_flatten_with_path(out)[0]
    names = [
        ".".join(str(_key_str(k)) for k in path) for path, _ in paths_leaves
    ]
    thunk = _Thunk(init_fn, args, kwargs, treedef, len(leaves), names)

    fake_leaves = [
        DeferredArray(leaf, thunk, i, names[i]) for i, leaf in enumerate(leaves)
    ]
    return jax.tree.unflatten(treedef, fake_leaves)


def _key_str(k) -> str:
    if hasattr(k, "key"):
        return str(k.key)
    if hasattr(k, "idx"):
        return str(k.idx)
    if hasattr(k, "name"):
        return str(k.name)
    return str(k)


def _cast_eligible(f: DeferredArray, thunk: _Thunk) -> bool:
    """Whether ``param_dtype`` applies to this leaf: floating, and in the
    ``params`` collection when the FULL recording has one (judged via the
    thunk so subtree and whole-tree materialization agree)."""
    if not jnp.issubdtype(f.dtype, jnp.floating):
        return False
    if thunk.has_params_collection():
        return f.path.split(".", 1)[0] == "params"
    return True


def _common_thunk(fakes: Sequence[DeferredArray]) -> _Thunk:
    thunks = {id(f._thunk): f._thunk for f in fakes}
    if len(thunks) != 1:
        raise ValueError(
            "All DeferredArrays in one materialize() call must come from the "
            "same deferred_init(); got arrays from "
            f"{len(thunks)} different recordings."
        )
    return next(iter(thunks.values()))


def materialize(
    tree: Any,
    *,
    mesh: Optional[Mesh] = None,
    plan: Optional[ShardingPlan] = None,
    specs: Optional[Any] = None,
    param_dtype=None,
):
    """Materialize a pytree of :class:`DeferredArray` into real (sharded)
    ``jax.Array``s.

    ``plan`` maps leaf paths to PartitionSpecs; alternatively ``specs`` may
    be a matching pytree of PartitionSpec.  One XLA program computes all
    requested leaves; with a mesh, every leaf lands pre-sharded (no host
    copy, no post-hoc reshard).

    ``param_dtype`` (e.g. ``jnp.bfloat16``) casts floating leaves inside
    the compiled program, mirroring the torch frontend's policy (init math
    at recorded precision, storage in ``param_dtype``).  When the FULL
    recording has a flax-style top-level ``params`` collection, only that
    collection is cast — other collections (``batch_stats`` etc.) keep
    full precision even when materialized as a subtree on their own;
    otherwise every floating leaf is cast.
    """
    fn, treedef = build_materialize_fn(
        tree, mesh=mesh, plan=plan, specs=specs, param_dtype=param_dtype
    )
    values = fn()
    return jax.tree.unflatten(treedef, list(values))


def materialize_parts(
    tree: Any,
    *,
    mesh: Optional[Mesh] = None,
    plan: Optional[ShardingPlan] = None,
    specs: Optional[Any] = None,
    param_dtype=None,
    init_dtype=None,
):
    """The raw pieces of a :func:`materialize` program, un-jitted:
    ``(run_fn, out_shardings, treedef)`` where ``run_fn()`` computes the
    selected leaves.  Callers that need to own the compile — the serving
    runtime routes replica param-init through
    ``compile_service.compile_program`` so the artifact registry
    and the compile-cache telemetry cover it — build on this;
    :func:`build_materialize_fn` is the plain-jit convenience on top.

    ``init_dtype`` arms the low-precision transport fast path
    (docs/performance.md §transport) for this program: leaves the
    ``param_dtype`` cast mask permits whose contract dtype is WIDER than
    ``init_dtype`` are computed/stored by the program in ``init_dtype``
    (halving the bytes moved).  The returned ``run_fn`` then delivers
    those leaves in ``init_dtype`` — the CALLER owns the on-device
    upcast (``transport.commit_outputs``; the serving
    bring-up in ``serve.engine.spin_up_replica`` does exactly this)."""
    fakes, treedef = jax.tree.flatten(tree, is_leaf=is_fake)
    for f in fakes:
        if not is_fake(f):
            raise ValueError(f"materialize() got a non-fake leaf: {type(f)!r}")
    thunk = _common_thunk(fakes)
    wanted = [f._leaf_idx for f in fakes]
    run_all = thunk.leaves_fn()

    elig = [_cast_eligible(f, thunk) for f in fakes]
    if param_dtype is not None:
        cast = elig
    else:
        cast = [False] * len(fakes)

    def run_selected():
        leaves = run_all()
        return tuple(
            leaves[i].astype(param_dtype) if c else leaves[i]
            for i, c in zip(wanted, cast)
        )

    if init_dtype is not None:
        finals = [
            jnp.dtype(param_dtype) if c else jnp.dtype(f.dtype)
            for f, c in zip(fakes, cast)
        ]
        run_selected = transport.wrap_storage(
            run_selected,
            transport.plan_transport(finals, elig, init_dtype),
        )

    out_shardings = None
    if mesh is not None:
        if specs is not None:
            spec_leaves = jax.tree.leaves(
                specs, is_leaf=lambda x: isinstance(x, PartitionSpec)
            )
            if len(spec_leaves) != len(fakes):
                raise ValueError(
                    f"specs pytree has {len(spec_leaves)} leaves, expected {len(fakes)}."
                )
            out_shardings = tuple(NamedSharding(mesh, s) for s in spec_leaves)
        else:
            plan = plan or ShardingPlan()
            out_shardings = tuple(
                NamedSharding(mesh, plan.spec_for(f.path, f.shape, mesh)) for f in fakes
            )
    return run_selected, out_shardings, treedef


def build_materialize_fn(
    tree: Any,
    *,
    mesh: Optional[Mesh] = None,
    plan: Optional[ShardingPlan] = None,
    specs: Optional[Any] = None,
    param_dtype=None,
):
    """The program-construction half of :func:`materialize`: returns
    ``(jitted_fn, treedef)`` WITHOUT executing.  A login host uses this
    to ``.lower()`` or ``jax.export`` the complete sharded init program
    for a pod slice it does not have (the JAX-frontend counterpart of
    jax_bridge.export's torch-module path)."""
    run_selected, out_shardings, treedef = materialize_parts(
        tree, mesh=mesh, plan=plan, specs=specs, param_dtype=param_dtype
    )
    if out_shardings is not None:
        fn = jax.jit(run_selected, out_shardings=out_shardings)
    else:
        fn = jax.jit(run_selected)
    return fn, treedef


def materialize_leaf(
    fake: DeferredArray,
    *,
    mesh: Optional[Mesh] = None,
    spec: Optional[PartitionSpec] = None,
    param_dtype=None,
) -> jax.Array:
    """Materialize a single leaf; XLA dead-code-eliminates everything the
    leaf does not depend on (the JAX-native ``materialize_tensor``).

    ``param_dtype`` follows the same policy as :func:`materialize`, so a
    leaf materialized alone has the same dtype it would in the batch."""
    if not is_fake(fake):
        raise ValueError("`fake` is not a DeferredArray.")
    run_all = fake._thunk.leaves_fn()
    idx = fake._leaf_idx
    do_cast = param_dtype is not None and _cast_eligible(fake, fake._thunk)

    def run_one():
        leaf = run_all()[idx]
        return leaf.astype(param_dtype) if do_cast else leaf

    if mesh is not None:
        fn = jax.jit(run_one, out_shardings=NamedSharding(mesh, spec or PartitionSpec()))
    else:
        fn = jax.jit(run_one)
    return fn()
