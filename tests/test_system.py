"""End-to-end behaviour tests for the paper's system."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCHS, get_config, get_smoke_config
from repro.core import cp_als, decide_partition, random_tensor


def test_end_to_end_decomposition_pipeline():
    """The paper's full pipeline: tensor → Fig.5 partition plan → chunked
    fixed-point CP-ALS → convergent decomposition."""
    st = random_tensor((64, 48, 80), 3000, seed=0)
    plan = decide_partition(st, rank=8, mem_bytes=64 * 1024, rank_axis=8)
    assert plan.capacity >= 1
    res = cp_als(st, 8, n_iters=3, engine="fixed", fixed_preset="int7",
                 chunk_shape=plan.chunk_shape, capacity=plan.capacity, seed=0)
    assert all(np.isfinite(f) for f in res.fit_history)
    assert res.diff_history[-1] <= res.diff_history[0] * 1.5


def test_all_archs_have_full_and_smoke_configs():
    for arch in ARCHS:
        full = get_config(arch)
        smoke = get_smoke_config(arch)
        assert full.family == smoke.family
        assert full.n_layers >= smoke.n_layers
        # smoke pattern exercises the same mixer set as the full pattern
        assert {s.mixer for s in smoke.pattern} == {s.mixer for s in full.pattern}


def test_dryrun_shape_registry_covers_assignment():
    from repro.launch.dryrun import SHAPES, should_skip
    assert set(SHAPES) == {"train_4k", "prefill_32k", "decode_32k",
                           "long_500k"}
    assert SHAPES["train_4k"] == dict(kind="train", seq=4096, batch=256)
    assert SHAPES["prefill_32k"] == dict(kind="prefill", seq=32768, batch=32)
    assert SHAPES["decode_32k"] == dict(kind="decode", seq=32768, batch=128)
    assert SHAPES["long_500k"] == dict(kind="decode", seq=524288, batch=1)
    # exactly the 6 pure-full-attention archs skip long_500k
    skips = [a for a in ARCHS if should_skip(get_config(a), "long_500k")]
    assert sorted(skips) == sorted([
        "qwen3_14b", "minitron_4b", "command_r_35b", "qwen3_moe_30b_a3b",
        "whisper_medium", "internvl2_1b"])


def test_serve_generation_end_to_end(trivial_mesh):
    from repro.launch.steps import generate, make_ctx
    from repro.models import LM
    cfg = get_smoke_config("qwen3_14b")
    lm = LM(cfg)
    ctx = make_ctx(trivial_mesh, seq_sharded=False)
    params, _ = lm.init(jax.random.key(0))
    prompts = jnp.ones((2, 8), jnp.int32)
    toks = generate(lm, params, ctx, prompts, gen=4)
    assert toks.shape == (2, 4)
    assert bool(jnp.all((toks >= 0) & (toks < cfg.vocab)))


@pytest.mark.parametrize("env_dir", [None, "from-env"])
def test_compile_cache_dir(tmp_path, monkeypatch, env_dir):
    """JAX_COMPILATION_CACHE_DIR wins untouched; without it the cache is
    the checkout's fixed .jax_cache."""
    from repro.launch.cache import CACHE_ENV, enable_compile_cache
    before = jax.config.jax_compilation_cache_dir
    try:
        if env_dir is None:
            monkeypatch.delenv(CACHE_ENV, raising=False)
            want = str(tmp_path / ".jax_cache")
            assert enable_compile_cache(tmp_path) == want
            assert jax.config.jax_compilation_cache_dir == want
        else:
            monkeypatch.setenv(CACHE_ENV, str(tmp_path / env_dir))
            assert enable_compile_cache(tmp_path) == str(tmp_path / env_dir)
            assert jax.config.jax_compilation_cache_dir == before
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
