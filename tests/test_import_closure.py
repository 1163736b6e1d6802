"""What a process has to import to compile a JAX program and get a logger.

The compile service (``torchdistx_tpu.compile_service``) and the
transport layer (``torchdistx_tpu.transport``) sit under BOTH frontends;
the torch bridge is one of their callers, not their home.  A serving
replica therefore loads neither torch nor orbax on its way up — 12 s of
every serving process's start when it did (PERF.md, PR 32).  Each case
is a fresh interpreter, because ``sys.modules`` of the test process has
long since seen torch.
"""

import os
import subprocess
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parent.parent

# ``google.cloud`` itself is a namespace package a .pth file loads at
# interpreter start, and ``utils.checkpoint`` is loaded by
# serve/rollover.py (manifest + verification, no orbax): neither can be
# the test.
_CLOSURE = '''
import sys

HEAVY = ("torch", "orbax", "tensorstore", "google.cloud.logging",
         "google.api_core", "torchdistx_tpu.jax_bridge")


def loaded():
    return sorted(m for m in sys.modules
                  if any(m == h or m.startswith(h + ".") for h in HEAVY))


def assert_light(after):
    assert not loaded(), f"{after} loaded {loaded()[:8]}"
'''

_CASES = {
    "serve_chaos_and_a_logger": '''
import torchdistx_tpu.serve
import torchdistx_tpu.chaos
from torchdistx_tpu.utils.logging import get_logger

get_logger().debug("up")
assert_light("import serve, chaos; get_logger()")
''',
    "compile_program_miss_then_hit": '''
import tempfile

import jax
import jax.numpy as jnp
import numpy as np

import torchdistx_tpu.config as tdx_config
from torchdistx_tpu import compile_service


def program(x):
    return jnp.tanh(x @ x.T).sum(axis=0) * 3.0


args = (jax.ShapeDtypeStruct((8, 8), jnp.float32),)
outcomes = []
with tempfile.TemporaryDirectory() as d, tdx_config.override(cache_dir=d):
    for _ in range(2):
        jax.clear_caches()
        compile_service.bind_cache()
        compiled, _, _, outcome, _ = compile_service.compile_program(
            program, args, None, init_compiler_options=False)
        outcomes.append(outcome)
    compile_service.reset_cache_binding()
assert outcomes == ["miss", "hit"], outcomes
x = np.arange(64, dtype=np.float32).reshape(8, 8) / 64.0
np.testing.assert_allclose(np.asarray(compiled(x)),
                           np.tanh(x @ x.T).sum(axis=0) * 3.0, rtol=1e-5)
assert_light("compile_service.compile_program")
''',
    "materialize_parts_low_precision_transport": '''
import jax
import jax.numpy as jnp

from torchdistx_tpu import abstract, transport


def init(key):
    return {"w": jax.random.normal(key, (8, 4), jnp.float32),
            "n": jnp.arange(4)}


tree = abstract.deferred_init(init, jax.random.PRNGKey(0))
run_fn, out_shardings, treedef = abstract.materialize_parts(
    tree, init_dtype=jnp.bfloat16)
values = jax.jit(run_fn)()
by_name = jax.tree.unflatten(treedef, list(values))
# the transport path: the eligible float leaf rides in the init dtype,
# the integer leaf is untouched
assert by_name["w"].dtype == jnp.bfloat16, by_name["w"].dtype
assert by_name["n"].dtype == jnp.arange(4).dtype
assert transport.resolve_init_dtype("bf16") == jnp.bfloat16
assert_light("abstract.materialize_parts(init_dtype=bfloat16)")
''',
    "utils_names_resolve_and_orbax_comes_with_the_save": '''
import tempfile

import numpy as np

import torchdistx_tpu.utils as utils

assert_light("import torchdistx_tpu.utils")
assert sorted(utils.__all__) == [
    "AsyncCheckpointSaver", "FailureDetector", "Metrics", "StepTimer",
    "Timer", "device_health", "get_logger", "restore_checkpoint",
    "run_elastic", "save_checkpoint", "trace"]
for name in utils.__all__:
    assert callable(getattr(utils, name)), name
assert utils.save_checkpoint.__module__ == "torchdistx_tpu.utils.checkpoint"
try:
    utils.no_such_name
except AttributeError:
    pass
else:
    raise AssertionError("unknown name resolved")
assert_light("resolving every public name of utils")

with tempfile.TemporaryDirectory() as d:
    utils.save_checkpoint(d + "/ckpt", {"a": np.arange(4.0)})
    back = utils.restore_checkpoint(d + "/ckpt", verify=True)
np.testing.assert_array_equal(back["a"], np.arange(4.0))
assert "orbax.checkpoint" in sys.modules
''',
    "no_import_of_the_bridge_below_it": '''
import re
from pathlib import Path

pkg = Path("torchdistx_tpu")
bridge = re.compile(r"^\\s*(from|import)\\s.*jax_bridge", re.M)
torch_ = re.compile(r"^\\s*(from|import)\\s+torch(\\s|\\.|$)", re.M)
below = [pkg / "abstract.py", pkg / "registry" / "store.py",
         pkg / "compile_service.py", pkg / "transport.py"]
for d in ("serve", "reshard", "parallel"):
    below += sorted((pkg / d).rglob("*.py"))
assert len(below) > 20, below
for f in below:
    hit = bridge.search(f.read_text())
    assert hit is None, f"{f}: {hit.group(0).strip()}"
for f in (pkg / "compile_service.py", pkg / "transport.py"):
    hit = torch_.search(f.read_text())
    assert hit is None, f"{f}: {hit.group(0).strip()}"
assert not (pkg / "jax_bridge" / "transport.py").exists()
''',
}


@pytest.mark.parametrize("case", sorted(_CASES))
def test_import_closure(case):
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=str(REPO),
               TDX_CACHE_DIR="", TDX_CACHE_MIN_COMPILE_S="0")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    env.pop("TDX_REGISTRY_DIR", None)
    out = subprocess.run(
        [sys.executable, "-c", _CLOSURE + _CASES[case]], cwd=str(REPO),
        env=env, capture_output=True, text=True, timeout=180,
    )
    assert out.returncode == 0, out.stderr[-3000:]
