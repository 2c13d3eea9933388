"""Where things land: the persistent compile cache's directory, and the
devices a router's engines occupy.  Each case runs in a child process so
this process's JAX config and device count stay untouched."""
import json
import os
import subprocess
import sys
import textwrap
from pathlib import Path

REPO = Path(__file__).resolve().parents[1]


def _run(code: str, **env_over) -> dict:
    env = dict(os.environ)
    env["PYTHONPATH"] = str(REPO / "src")
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    env.update(env_over)
    r = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                       env=env, capture_output=True, text=True, timeout=300)
    assert r.returncode == 0, r.stderr[-3000:]
    return json.loads(r.stdout.strip().splitlines()[-1])


def test_compile_cache_follows_env_dir(tmp_path):
    out = _run("""
        import json, jax, jax.numpy as jnp
        from repro.launch.compile_cache import enable_compile_cache
        d = enable_compile_cache()
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        jax.jit(lambda x: x * 2 + 1)(jnp.ones(8)).block_until_ready()
        print(json.dumps({"dir": d}))
    """, JAX_COMPILATION_CACHE_DIR=str(tmp_path))
    assert out["dir"] == str(tmp_path)
    assert any(tmp_path.iterdir()), "no cache entry written"


def test_compile_cache_defaults_to_checkout():
    out = _run("""
        import json, jax
        from repro.launch.compile_cache import enable_compile_cache
        d = enable_compile_cache()
        print(json.dumps({"dir": d,
                          "cfg": jax.config.jax_compilation_cache_dir}))
    """)
    assert out["dir"] == out["cfg"] == str(REPO / ".jax_cache")


def test_router_places_one_engine_per_device():
    out = _run("""
        import json, jax
        import numpy as np
        from repro.configs import get_arch
        from repro.models import build_model
        from repro.serve import (CacheConfig, CubeRouter, EngineConfig,
                                 Request, ServeEngine)

        cfg = get_arch("qwen2.5-3b").reduced()
        model = build_model(cfg)
        params = model.init(jax.random.key(0))
        ecfg = EngineConfig(batch_slots=2, max_len=32,
                            cache=CacheConfig(page_size=4))
        rng = np.random.default_rng(0)
        prompts = [rng.integers(0, cfg.vocab_size, size=(5 + i,))
                   .astype(np.int32) for i in range(4)]
        reqs = lambda: [Request(uid=i, prompt=p, max_new_tokens=4)
                        for i, p in enumerate(prompts)]
        one = ServeEngine(model, params, ecfg)
        for r in reqs():
            one.submit(r)
        want = {r.uid: r.out_tokens for r in one.run()}
        devs = jax.devices()
        router = CubeRouter(model, params, ecfg, n_cubes=2, devices=devs)
        for r in reqs():
            router.submit(r)
        got = {r.uid: r.out_tokens for r in router.run()}
        placed = []
        for e in router.engines:
            on = {d for x in jax.tree.leaves((e.params, e.cache.pools))
                  for d in x.devices()}
            placed.append(sorted(str(d) for d in on))
        print(json.dumps({"same": got == want, "placed": placed,
                          "devs": [str(d) for d in devs]}))
    """, XLA_FLAGS="--xla_force_host_platform_device_count=2")
    assert out["same"]
    assert out["placed"] == [[d] for d in out["devs"]]
