"""chip_smoke.py's phases on CPU at reduced width (interpret-mode kernels),
its refusal to run without a TPU, and the compile-cache placement that the
smoke and the serve CLI share."""
import dataclasses
import importlib.util
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load_smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(REPO, "chip_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


cs = _load_smoke()


def _reduced(arch=cs.ARCH):
    # 4 layers: int8 first/last blocks around a ternary body, so both of
    # the "mixed" policy's cells are served
    return dataclasses.replace(cs.model_config(arch, reduced=True),
                               n_layers=4)


def test_main_refuses_without_tpu(capsys):
    assert cs.main([]) == 2
    out = capsys.readouterr()
    assert '"ok"' not in out.out and "needs a TPU" in out.err


def test_one_chip_phases_reduced():
    from repro.launch.serve import serve_params
    cfg = _reduced()
    sparams, _ = serve_params(cfg, seed=cs.SEED)
    lines = []
    res = cs.one_chip(cfg, sparams, lines.append)
    assert res["requests"] == len(cs.PROMPT_LENS)
    assert res["tokens"] == len(cs.PROMPT_LENS) * cs.MAX_NEW
    assert res["logit_rel_l2"] <= cs.LOGIT_REL_TOL
    cells = {c["cell"] for c in cs.check_cells(cfg, sparams, rows=(8,))}
    assert cells == {"wter/ater/popcount", "wint/aint/*"}, cells
    # on CPU the two paths compile to the same arithmetic: exact agreement
    assert res["first_token_agree"] == len(cs.PROMPT_LENS)
    assert res["logit_rel_l2"] == 0.0
    assert res["layer_rel_l2"] == [0.0] * cfg.n_layers
    assert set(res["free_rel_l2"].values()) == {0.0}
    assert any("first tokens pallas vs jnp" in ln for ln in lines)


def test_teacher_forced_catches_head_fault(monkeypatch):
    """A flash kernel that hands heads back in the wrong order moves the
    teacher-forced layer updates by O(1), far past LOGIT_REL_TOL."""
    import jax.numpy as jnp
    from repro.kernels import flash_attn
    from repro.launch.serve import serve_params
    cfg = _reduced()
    sparams, _ = serve_params(cfg, seed=cs.SEED)
    prompt = cs.prompts(cfg, (240,))[0]
    ctxs = cs.serve_ctx("pallas"), cs.serve_ctx("jnp")
    rels, got, want = cs.teacher_forced(cfg, sparams, prompt, 256, *ctxs)
    assert rels == [0.0] * cfg.n_layers and (got == want).all()
    flash = flash_attn.flash_attention
    monkeypatch.setattr(flash_attn, "flash_attention",
                        lambda q, k, v, **kw: jnp.flip(flash(q, k, v, **kw),
                                                       0))
    rels, _, _ = cs.teacher_forced(cfg, sparams, prompt, 256, *ctxs)
    assert min(rels) > 10 * cs.LOGIT_REL_TOL, rels


def test_check_cells_refuses_bodiless_cell():
    """A policy whose layers have no Pallas body (weight-only cells) must
    fail the smoke instead of quietly running XLA."""
    from repro.launch.serve import serve_params
    cfg = dataclasses.replace(_reduced(), policy="w-ternary")
    sparams, _ = serve_params(cfg, seed=cs.SEED)
    with pytest.raises(cs.SmokeFailure, match="no Pallas body"):
        cs.check_cells(cfg, sparams, rows=(8,))


def test_compare_logits_bound():
    import numpy as np
    want = np.ones(64, np.float32)
    assert cs.compare_logits(want * 1.01, want) == pytest.approx(0.01)
    with pytest.raises(cs.SmokeFailure, match="relative L2"):
        cs.compare_logits(-want, want)
    with pytest.raises(cs.SmokeFailure, match="non-finite"):
        cs.compare_logits(want * np.nan, want)


FOUR = r'''
import dataclasses, os, sys
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
sys.path.insert(0, __REPO__)
import jax
import chip_smoke as cs
from repro.launch.mesh import make_mesh
from repro.launch.serve import serve_params
mesh = make_mesh((1, 4), ("data", "model"), devices=jax.devices()[:4])
red = lambda arch: dataclasses.replace(cs.model_config(arch, reduced=True),
                                       n_layers=4)
cfg = red(cs.ARCH)
cs.tp_phase(cfg, serve_params(cfg, seed=0)[0], mesh, cache_len=256,
            max_new=4)
cs.ep_phase(red(cs.MOE_ARCH), mesh, max_new=4)
print("FOUR_CHIP_PHASES_OK")
'''


def test_four_chip_phases_reduced():
    """TP (token-exact vs one device) and EP with params created sharded
    (token- and stat-exact vs the dense expert vmap) on 4 CPU devices."""
    env = {**os.environ, "PYTHONPATH": os.path.join(REPO, "src")}
    r = subprocess.run(
        [sys.executable, "-c", FOUR.replace("__REPO__", repr(REPO))],
        capture_output=True, text=True, env=env, cwd=REPO, timeout=900)
    assert r.returncode == 0, r.stderr[-3000:]
    assert "FOUR_CHIP_PHASES_OK" in r.stdout


CACHE = r'''
import jax
from repro.launch import serve
print("DIR", serve.configure_compile_cache())
print("CFG", jax.config.jax_compilation_cache_dir)
'''


@pytest.mark.parametrize("env_dir", [True, False], ids=["env", "default"])
def test_compile_cache_dir(tmp_path, env_dir):
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    if env_dir:
        env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path)
    r = subprocess.run([sys.executable, "-c", CACHE], capture_output=True,
                       text=True, env=env, cwd=REPO, timeout=300)
    assert r.returncode == 0, r.stderr[-3000:]
    out = dict(ln.split(" ", 1) for ln in r.stdout.splitlines())
    want = str(tmp_path) if env_dir else os.path.join(REPO, ".jax_cache")
    assert out["DIR"] == want and out["CFG"] == want
