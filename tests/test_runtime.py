"""Backend-driven kernel mode, the TPU no-oracle rule and the compile cache
(repro.kernels.runtime)."""
import pathlib
import re

import jax
import pytest

from repro.kernels import runtime
from repro.kernels.runtime import enable_compile_cache, resolve_interpret

REPO = pathlib.Path(__file__).resolve().parents[1]
SRC = REPO / "src" / "repro"


@pytest.fixture
def backend(monkeypatch):
    """Steer ``jax.default_backend()`` for the code under test."""
    def set_backend(name):
        monkeypatch.setattr(jax, "default_backend", lambda: name)
    return set_backend


@pytest.mark.parametrize("name,value,expect", [
    ("cpu", None, True), ("tpu", None, False), ("gpu", None, True),
    ("cpu", True, True), ("cpu", False, False),
    ("tpu", True, True), ("tpu", False, False),
    ("gpu", True, True), ("gpu", False, False),
])
def test_interpret_follows_backend(backend, name, value, expect):
    """``None`` compiles on the TPU and interprets elsewhere; an explicit
    argument wins on every backend."""
    backend(name)
    assert resolve_interpret(value) is expect


def _lut_acu(**kw):
    from repro.core.acu import make_acu
    return make_acu("mul8s_1L2H", "lut", **kw)


@pytest.mark.parametrize("name,use_pallas,fused,route", [
    ("cpu", False, False, "lut_jnp_oracle"),
    ("cpu", True, True, "fused_lut_dense"),
    ("tpu", True, True, "fused_lut_dense"),
    ("tpu", True, False, "lut_matmul"),
    ("tpu", False, False, None),
    ("tpu", False, True, None),
])
def test_gemm_plan_never_an_oracle_on_tpu(backend, name, use_pallas, fused,
                                          route):
    from repro.core.acu import matmul_plan
    backend(name)
    acu = _lut_acu(use_pallas=use_pallas, fused=fused)
    if route is None:
        with pytest.raises(RuntimeError, match="no kernel route on the TPU"):
            matmul_plan(acu, mesh=False)
    else:
        assert matmul_plan(acu, mesh=False).route == route


@pytest.mark.parametrize("name,use_pallas,pin,route", [
    ("cpu", False, None, "dense"),
    ("tpu", True, None, "fused_attn"),
    ("tpu", False, "dense", "dense"),
    ("tpu", False, None, None),
])
def test_attn_plan_never_falls_back_on_tpu(backend, name, use_pallas, pin,
                                           route):
    from repro.core.acu import AttnSpec, attn_plan
    backend(name)
    acu = _lut_acu(use_pallas=use_pallas)
    spec = AttnSpec(hq=9, hkv=3)
    if route is None:
        with pytest.raises(RuntimeError, match="no kernel route on the TPU"):
            attn_plan(acu, spec, mesh=False, route=pin)
    else:
        assert attn_plan(acu, spec, mesh=False, route=pin).route == route


def test_functional_gemm_raises_on_tpu(backend):
    from repro.core.acu import make_acu, matmul_plan
    backend("tpu")
    with pytest.raises(RuntimeError, match="FUNCTIONAL GEMM"):
        matmul_plan(make_acu("mul8s_1L2H", "functional"), mesh=False)


@pytest.mark.parametrize("spec,fused", [("mul8s_1L2H:lut", True),
                                        ("mul8s_trunc2:factored", False)])
def test_launchers_build_kernel_routes(spec, fused):
    """make_acfg (both launchers) puts every ACU on its Pallas routes and
    LUT mode on the fused kernel."""
    from repro.launch.specs import make_acfg
    acfg = make_acfg(spec, approx_bwd=True)
    assert acfg.acu.use_pallas and acfg.acu.fused is fused
    assert acfg.acu.interpret is None and acfg.approx_bwd
    assert make_acfg(None) is None


def test_compile_cache_honours_env(monkeypatch, tmp_path):
    """With JAX_COMPILATION_CACHE_DIR set, that directory is the cache and
    no code configures another."""
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


@pytest.mark.parametrize("env", [None, ""])
def test_compile_cache_default_is_fixed_in_repo(monkeypatch, env):
    """Without the variable the cache is ``<checkout>/.jax_cache``: the
    same path on every call, never built from a temporary name."""
    if env is None:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    else:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", env)
    before = jax.config.jax_compilation_cache_dir
    try:
        path = enable_compile_cache()
        assert path == str(REPO / ".jax_cache") == enable_compile_cache()
        assert jax.config.jax_compilation_cache_dir == path
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
    assert runtime.DEFAULT_CACHE_DIR.parent == REPO
    assert ".jax_cache/" in (REPO / ".gitignore").read_text().split()


def test_no_hardcoded_interpret_defaults():
    """No kernel wrapper may regress to ``interpret: bool = True`` — the
    default lives in runtime.resolve_interpret, which follows the
    backend."""
    pat = re.compile(r"interpret\s*:\s*bool\s*=\s*(True|False)")
    offenders = []
    for path in SRC.rglob("*.py"):
        for i, line in enumerate(path.read_text().splitlines(), 1):
            if pat.search(line):
                offenders.append(f"{path.relative_to(SRC)}:{i}: {line.strip()}")
    assert not offenders, "\n".join(offenders)


def test_every_pallas_call_resolves():
    """Every ``pallas_call(... interpret=...)`` site must route through
    resolve_interpret: one ``interpret=resolve_interpret(`` per call."""
    n_files = 0
    for path in SRC.rglob("*.py"):
        src = path.read_text()
        calls = len(re.findall(r"pallas_call\(", src))
        if not calls:
            continue
        n_files += 1
        resolved = len(re.findall(r"interpret=resolve_interpret\(", src))
        assert resolved == calls, \
            f"{path}: {calls} pallas_call sites, {resolved} resolved"
    assert n_files >= 8
