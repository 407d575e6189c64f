"""The program's own tracing (``repro.obs``): the scope names reach the
lowered HLO where the work happens, ``GenServer`` ticks leave their
``gen.*`` spans in a profiler trace, and the compile counter counts each
new executable once."""

import glob
import os
import re
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro import obs
from repro.launch import train_recipes
from repro.launch.serve_gen import GenServer
from repro.models import dcgan, enet, espnet


def _scopes(hlo: str) -> set[str]:
    return set(re.findall(r"(?:engine|layout|grad|train|esp)\.[a-z_]+", hlo))


@pytest.fixture(scope="module")
def enet_params():
    return enet.init_params(jax.random.PRNGKey(0), num_classes=5)


@pytest.mark.parametrize("backend", ["xla", "pallas"])
def test_enet_forward_names_every_engine_and_layout_pass(enet_params,
                                                         backend):
    """64x64 puts every engine in the network: the strided stem (dense),
    the dilated bottlenecks and the transposed upsamplers."""
    x = jnp.zeros((1, 64, 64, 3), jnp.float32)
    f = jax.jit(lambda p, x: enet.forward(p, x, backend=backend))
    names = _scopes(f.lower(enet_params, x).as_text(debug_info=True))
    assert set(obs.ENGINES) <= names
    assert {obs.LAYOUT_PHASE_SPLIT, obs.LAYOUT_PHASE_STITCH,
            obs.LAYOUT_PARITY_INTERLEAVE} <= names
    if backend == "pallas":
        assert {obs.LAYOUT_PAD, obs.LAYOUT_CROP} <= names


def test_espnet_forward_names_its_merges_and_reinforcement():
    """The ESP modules' merges and the input reinforcement carry their
    ``esp.*`` scopes, beside the engines'."""
    params = espnet.init_params(jax.random.PRNGKey(0), num_classes=5)
    x = jnp.zeros((1, 32, 64, 3), jnp.float32)
    hlo = jax.jit(espnet.forward).lower(params, x).as_text(debug_info=True)
    assert {obs.ESP_MERGE, obs.ESP_REINFORCE} | set(obs.ENGINES) \
        <= _scopes(hlo)
    assert "esp.merge/concatenate" in hlo
    assert "esp.reinforce/reduce_window" in hlo


def test_enet_train_step_names_gradients_loss_and_optimizer(enet_params):
    step = train_recipes.make_train_step("enet", backend="pallas")
    state = train_recipes.init_state(enet_params)
    batch = {"image": jnp.zeros((1, 32, 32, 3), jnp.float32),
             "label": jnp.zeros((1, 32, 32), jnp.int32)}
    names = _scopes(step.lower(state, batch).as_text(debug_info=True))
    assert {obs.GRAD_DW, obs.GRAD_DX, obs.TRAIN_LOSS,
            obs.TRAIN_OPTIMIZER} <= names
    assert set(obs.ENGINES) <= names


def test_gen_server_tick_leaves_its_spans_in_order(tmp_path):
    params = dcgan.init_params(jax.random.PRNGKey(1), size=64, nz=12, ngf=4)
    srv = GenServer(batch=2, dcgan_nz=12, params={"dcgan64": params})
    for i in range(2):
        srv.submit("dcgan64", seed=i)
    srv.run()                                   # compiles outside the trace
    for i in range(2):
        srv.submit("dcgan64", seed=10 + i)
    calls0 = srv.stats()["admit_calls"]
    jax.profiler.start_trace(str(tmp_path))
    try:
        srv.step()
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    events = sorted((e.start_ns, e.end_ns, e.name, dict(e.stats))
                    for plane in ProfileData.from_file(path).planes
                    for line in plane.lines for e in line.events
                    if e.name.startswith("gen."))
    assert [n for _, _, n, _ in events] == [
        obs.GEN_EXPIRE, obs.GEN_ADMIT, obs.GEN_DISPATCH, obs.GEN_FETCH]
    assert all(st["tick"] == 1 for _, _, _, st in events)
    assert events[1][3]["admitted"] == 2
    assert events[1][3]["refills"] == 1 == srv.stats()["admit_calls"] - calls0
    assert all(e0 <= s1 for (_, e0, _, _), (s1, _, _, _)
               in zip(events, events[1:]))


def test_compile_counter_moves_once_for_a_new_shape():
    f = jax.jit(lambda x: x * 2.0 + 1.0)
    x = np.ones((7, 13), np.float32)             # no jnp op: no compile
    c0 = obs.compiles()
    f(x)
    assert obs.compiles() == c0 + 1
    f(x)
    f(x + 1.0)
    assert obs.compiles() == c0 + 1
    f(np.ones((7, 14), np.float32))
    assert obs.compiles() == c0 + 2


def test_compile_counter_counts_a_persistent_cache_load_once(tmp_path):
    """A load from the persistent cache is a new executable for the
    process: counted once, though JAX also reports it as a cache hit."""
    script = textwrap.dedent(f"""
        import jax, numpy as np
        from jax import monitoring
        from repro import obs
        jax.config.update("jax_compilation_cache_dir", {str(tmp_path)!r})
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
        hits = []
        monitoring.register_event_listener(
            lambda e, **kw: hits.append(e)
            if e == "/jax/compilation_cache/cache_hits" else None)
        f = lambda x: x * 3.0 - 1.0
        x = np.ones((5, 11), np.float32)
        jax.jit(f)(x)
        c1 = obs.compiles()
        jax.clear_caches()
        jax.jit(f)(x)
        print(c1, obs.compiles() - c1, len(hits))
    """)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.pathsep.join(p for p in sys.path if p))
    out = subprocess.run([sys.executable, "-c", script], env=env,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr
    first, second, hits = map(int, out.stdout.split())
    assert (first, second, hits) == (1, 1, 1)


def test_cold_ticks_are_those_that_compiled():
    params = dcgan.init_params(jax.random.PRNGKey(2), size=64, nz=11, ngf=4)
    srv = GenServer(batch=2, dcgan_nz=11, params={"dcgan64": params})
    for i in range(6):
        srv.submit("dcgan64", seed=i)
    srv.run()
    cold = [t[4] for t in srv._tick_log]
    assert cold[0] and not any(cold[1:])
    st = srv.stats()
    assert st["compiles"] >= 1
    assert st["warm_wall_s"] == pytest.approx(
        sum(t[0] for t in srv._tick_log[1:]))
