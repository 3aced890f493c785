"""Tensor-parallel serving lockdown: the packed continuous-batching server on
a ("data", "model") mesh must be TOKEN-EXACT against the single-device server
for every W&A policy on both qgemm backends.

Why exactness is achievable (and therefore demanded): the only cross-shard
reduction the TP serve path performs is the row-parallel psum, and it runs on
the int32 accumulator BEFORE requant — integer addition is associative, so
the sharded sum equals the single-device sum bit for bit. Activation prep
runs replicated (full-K) inside shard_map, requant is elementwise, and no
float reduction axis is ever sharded. Any relaxation of that discipline
(psum after requant, partial-K activation stats, a float psum) shows up here
as a token mismatch, not a tolerance warning.

Runs in a subprocess with --xla_force_host_platform_device_count=8 (same
pattern as test_multidevice.py) so the device-count flag can't leak into the
rest of the suite. Same seeds/requests as test_serving.py.
"""
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

SCRIPT = r'''
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import dataclasses
import jax, jax.numpy as jnp, numpy as np
assert len(jax.devices()) == 8
from repro.configs import get_config
from repro.launch.serve import Request, Server
from repro.models import transformer
from repro.models.common import ModelCtx

MODEL = __TP__                      # TP degree; data = 8 // MODEL
from repro.launch.mesh import make_mesh
mesh = make_mesh((8 // MODEL, MODEL), ("data", "model"))

# same traffic as tests/test_serving.py
PROMPT_LENS, MAX_NEW, CACHE_LEN, PAGE_SIZE = (3, 9, 14), 4, 32, 4
# 24 pages: ample for this traffic AND divisible by data=2/4 so the paged
# pool really device-shards over the data axis (the default slots*8+1 pool
# is odd and would fall back to replicated); used for BOTH runs so the
# admission schedule is identical
NUM_PAGES = 24

rng = np.random.default_rng(7)

def serve(cfg, sparams, ctx, prompts, mesh_):
    srv = Server(cfg, sparams, slots=2, cache_len=CACHE_LEN, paged=True,
                 page_size=PAGE_SIZE, num_pages=NUM_PAGES, ctx=ctx, mesh=mesh_)
    if mesh_ is not None:
        # the pool was placed per-data-shard at construction (page axis over
        # "data") while the host PageTable stays global numpy
        sh = srv.cache["first"]["k"].sharding
        assert sh.spec[0] == "data", sh
        assert isinstance(srv.pt.table, np.ndarray)
    for i, p in enumerate(prompts):
        srv.submit(Request(i, p, MAX_NEW))
    srv.run()
    assert len(srv.completed) == len(prompts)
    # jit discipline survives TP: one decode signature, bucketed prefill
    assert srv.compile_counts["decode"] == 1, srv.compile_counts
    return srv

for policy in ("binary", "ternary", "int8"):
    cfg = dataclasses.replace(get_config("llama3.2-3b").reduced(), policy=policy)
    params = transformer.init(jax.random.PRNGKey(0), cfg)
    sparams = transformer.pack_for_serve(params, cfg)
    prompts = [rng.integers(0, cfg.vocab, size=(n,)).astype(np.int32)
               for n in PROMPT_LENS]
    for backend in ("jnp", "pallas"):
        ctx = ModelCtx(mode="serve", backend=backend, dtype=jnp.float32)
        want = {r.rid: r.out for r in
                serve(cfg, sparams, ctx, prompts, None).completed}
        tp_srv = serve(cfg, sparams, ctx, prompts, mesh)
        got = {r.rid: r.out for r in tp_srv.completed}
        assert got == want, ("TP serve diverged", MODEL, policy, backend,
                             got, want)
        assert tp_srv.pt.free_pages == tp_srv.pt.usable_pages
        print("OK", MODEL, policy, backend, flush=True)
print("SERVING_TP_OK", MODEL)
'''


@pytest.mark.parametrize("tp", [2, 4])
def test_tp_serve_token_exact_vs_single_device(tp):
    """TP(model=2,4) x {binary,ternary,int8} x {jnp,pallas}: sharded paged
    serve == single-device serve, token for token, on a forced-8-device CPU
    mesh; pool sharded over "data", PageTable host-global."""
    env = {**os.environ, "PYTHONPATH": os.path.join(REPO, "src")}
    r = subprocess.run([sys.executable, "-c", SCRIPT.replace("__TP__", str(tp))],
                       capture_output=True, text=True, env=env, cwd=REPO,
                       timeout=900)
    assert r.returncode == 0, r.stderr[-4000:]
    assert f"SERVING_TP_OK {tp}" in r.stdout, r.stdout[-2000:]


SCRIPT_ODD = r'''
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import dataclasses
import jax, jax.numpy as jnp, numpy as np
assert len(jax.devices()) == 8
from repro.configs import get_config
from repro.launch.serve import Request, Server
from repro.models import transformer
from repro.models.common import ModelCtx

# slots=3 over data=2: the non-dividing batch that the CPU SPMD partitioner
# silently miscompiled (wrong tokens, no error) before the inert phys-slot
# padding. 3 prompts so all three slots really co-run.
from repro.launch.mesh import make_mesh
mesh = make_mesh((2, 4), ("data", "model"))
PROMPT_LENS, MAX_NEW, CACHE_LEN, PAGE_SIZE = (3, 9, 14), 4, 32, 4
NUM_PAGES = 24
rng = np.random.default_rng(7)
cfg = dataclasses.replace(get_config("llama3.2-3b").reduced(),
                          policy="ternary")
params = transformer.init(jax.random.PRNGKey(0), cfg)
sparams = transformer.pack_for_serve(params, cfg)
prompts = [rng.integers(0, cfg.vocab, size=(n,)).astype(np.int32)
           for n in PROMPT_LENS]
ctx = ModelCtx(mode="serve", backend="jnp", dtype=jnp.float32)

def serve(mesh_):
    srv = Server(cfg, sparams, slots=3, cache_len=CACHE_LEN, paged=True,
                 page_size=PAGE_SIZE, num_pages=NUM_PAGES, ctx=ctx,
                 mesh=mesh_)
    # the device batch pads to the next data-axis multiple; host-side
    # scheduling stays at 3 slots
    assert srv.phys_slots == (4 if mesh_ is not None else 3), srv.phys_slots
    for i, p in enumerate(prompts):
        srv.submit(Request(i, p, MAX_NEW))
    srv.run()
    assert len(srv.completed) == 3
    assert srv.pt.free_pages == srv.pt.usable_pages
    return {r.rid: r.out for r in srv.completed}

want = serve(None)
got = serve(mesh)
assert got == want, ("odd-slots TP serve diverged", got, want)
print("ODD_SLOTS_OK")
'''


def test_tp_odd_slots_vs_single_device():
    """slots=3 on a data=2 mesh — the divisibility regression: before the
    inert phys-slot padding, the CPU SPMD partitioner produced WRONG TOKENS
    (silently) for any slot count not dividing the data axis. Now the device
    batch pads to phys_slots=4 and the tokens must match single-device
    serving exactly."""
    env = {**os.environ, "PYTHONPATH": os.path.join(REPO, "src")}
    r = subprocess.run([sys.executable, "-c", SCRIPT_ODD],
                       capture_output=True, text=True, env=env, cwd=REPO,
                       timeout=900)
    assert r.returncode == 0, r.stderr[-4000:]
    assert "ODD_SLOTS_OK" in r.stdout, r.stdout[-2000:]
