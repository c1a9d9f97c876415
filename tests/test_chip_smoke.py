"""chip_smoke.py on the CPU: its phase functions at opt-tiny (resident
through ServeFront over a socket, streamed with window rotation, tokens
equal between them), its refusal to run without a TPU, the compile cache
directory rule, and the serve CLI's name resolution of the paper's
published widths."""
from __future__ import annotations

import importlib.util
from pathlib import Path

import jax
import pytest

from repro.configs.paper_models import OPT_FAMILY, OPT_TINY
from repro.launch.serve import enable_compile_cache, resolve_config

ROOT = Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_phases_at_opt_tiny_agree(smoke):
    """Resident over real sockets vs streamed under a budget that holds
    the prefetch windows and lm_head but no cached layer group."""
    lines = []
    out = smoke.run_one_chip("opt-tiny", seed=0, budget_mib=0.6,
                             log=lines.append)
    st = out["stream"]
    assert st["groups_streamed"] > OPT_TINY.n_layers      # windows rotated
    assert st["cache_hits"] == 0 and st["pool_uploads"] > 0
    assert st["fetch_faults"] == st["fetch_retries"] == 0
    assert [len(t) for t in out["tokens"]] == [smoke.MAX_NEW] * \
        smoke.N_REQUESTS
    assert any(line.startswith("reference: ") for line in lines)
    assert any("streamed tokens == resident tokens" in line
               for line in lines)


def test_prompts_follow_the_seed(smoke):
    a = smoke.make_prompts(OPT_TINY.vocab_size, seed=3)
    assert a == smoke.make_prompts(OPT_TINY.vocab_size, seed=3)
    assert a != smoke.make_prompts(OPT_TINY.vocab_size, seed=4)
    lo, hi = smoke.PROMPT_LEN
    assert len(a) == smoke.N_REQUESTS
    assert all(lo <= len(p) <= hi for p in a)
    assert all(1 <= t < OPT_TINY.vocab_size for p in a for t in p)


def test_main_refuses_cpu_only_process(smoke, capsys):
    assert jax.devices()[0].platform == "cpu"
    with pytest.raises(SystemExit) as exc:
        smoke.main([])
    assert exc.value.code not in (0, None)
    assert '"ok"' not in capsys.readouterr().out


@pytest.mark.parametrize("env_dir", [None, "from-env"])
def test_compile_cache_dir(env_dir, tmp_path, monkeypatch):
    """JAX_COMPILATION_CACHE_DIR wins and nothing else is set; otherwise
    one fixed .jax_cache/ under the given checkout root."""
    before = jax.config.jax_compilation_cache_dir
    if env_dir is None:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    else:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", env_dir)
    try:
        got = enable_compile_cache(tmp_path)
        if env_dir is None:
            assert got == str(tmp_path / ".jax_cache")
            assert jax.config.jax_compilation_cache_dir == got
            assert enable_compile_cache(tmp_path) == got
        else:
            assert got == env_dir
            assert jax.config.jax_compilation_cache_dir == before
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


def test_serve_resolves_published_opt_widths():
    cfg = resolve_config("opt-1.3b")
    assert (cfg.n_layers, cfg.d_model, cfg.n_heads, cfg.d_ff,
            cfg.vocab_size) == (24, 2048, 32, 8192, 50272)
    assert all(resolve_config(c.name) is c for c in OPT_FAMILY)
    assert resolve_config("opt-tiny") is OPT_TINY
    with pytest.raises(SystemExit, match="published widths"):
        resolve_config("opt-1.3b", smoke=True)
    with pytest.raises(SystemExit, match="unknown arch"):
        resolve_config("opt-1.3")
