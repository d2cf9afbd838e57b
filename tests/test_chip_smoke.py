"""CPU rehearsal of ``chip_smoke.py`` and of what keeps it honest.

The phases run here at a tiny size with interpret-mode kernels and hold
the system to the same parity and finiteness checks as on the chip; the
script itself must refuse to run, and print no result, without a TPU.
"""
import dataclasses
import importlib.util
import json
import os
import subprocess
import sys
import textwrap

import pytest

from repro.configs.base import ParamCfg
from repro.nn.recurrent import LSTMConfig

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SCRIPT = os.path.join(REPO, "chip_smoke.py")


def _load(name, path):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod   # dataclasses resolve annotations through it
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def chip_smoke():
    return _load("chip_smoke", SCRIPT)


def _tiny(cs):
    # 7 clients + the scratch row = 8 arena rows, which divide over 4
    return cs.Setup(
        model=LSTMConfig(vocab=20, embed=8, hidden=32,
                         param=ParamCfg(min_dim_for_factorization=8)),
        clients=7, cohort=4, chunk=4, batch=4, seq_len=10,
        samples_per_client=8, test_seqs=16, rounds=2)


def test_smoke_phase_matches_reference_on_cpu(chip_smoke):
    out = chip_smoke.smoke_phase(_tiny(chip_smoke))
    exact = out["highest_precision"]
    assert exact["param_max_quanta"] <= chip_smoke.MATCHED.max_quanta
    assert exact["param_mean_quanta"] <= chip_smoke.MATCHED.mean_quanta
    assert exact["up_bytes"] > 0 and exact["down_bytes"] > 0
    assert len(out["streaming"]["steady_round_s"]) == 1
    assert exact["eval"][-1] < exact["eval"][0]   # the LSTM learns
    # the CPU's default matmul precision is fp32: no drift to allow for
    floor = out["reference_default_vs_highest"]
    assert floor["param_max_quanta"] == floor["eval_loss_diff"] == 0
    assert out["default_bounds"] == dataclasses.asdict(chip_smoke.MATCHED)


def test_compare_rejects_a_diverged_model(chip_smoke):
    s = _tiny(chip_smoke)
    task = chip_smoke._task(s)
    a = chip_smoke.make_server(s, task, engine="sequential", use_pallas=False)
    b = chip_smoke.make_server(s, task, engine="sequential", use_pallas=False)
    recs_a, _ = chip_smoke.run_rounds(a, 1)
    recs_b, _ = chip_smoke.run_rounds(b, 1)
    tight = {"run": chip_smoke.MATCHED}
    same = chip_smoke.compare(a, recs_a, b, recs_b)
    chip_smoke.check({"run": same}, tight)   # identical runs agree
    assert same["param_max_quanta"] == same["train_loss_diff"] == 0
    import jax

    a.global_params = jax.tree.map(lambda x: x * 1.01, a.global_params)
    off = chip_smoke.compare(a, recs_a, b, recs_b)
    with pytest.raises(chip_smoke.ParityError, match="run.param_max_quanta"):
        chip_smoke.check({"run": off}, tight)

    recs_a[0] = dict(recs_a[0], up_bytes=recs_a[0]["up_bytes"] + 1)
    with pytest.raises(chip_smoke.ParityError, match="wire bytes"):
        chip_smoke.compare(a, recs_a, b, recs_b)


def test_mesh_phase_shards_arena_and_chunk_on_virtual_devices():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.path.join(REPO, "src"))
    body = textwrap.dedent(f"""
        import importlib.util, json, sys, jax, numpy as np
        from jax.sharding import Mesh
        from repro.configs.base import ParamCfg
        from repro.fl.arena import ClientArena
        from repro.nn.recurrent import LSTMConfig
        spec = importlib.util.spec_from_file_location('cs', {SCRIPT!r})
        cs = importlib.util.module_from_spec(spec)
        sys.modules['cs'] = cs
        spec.loader.exec_module(cs)
        s = cs.Setup(model=LSTMConfig(vocab=20, embed=8, hidden=32,
                                      param=ParamCfg(min_dim_for_factorization=8)),
                     clients=7, cohort=4, chunk=4, batch=4, seq_len=10,
                     samples_per_client=8, test_seqs=16, rounds=2)
        out = cs.mesh_phase(jax.devices()[:4], s)
        try:   # 9 rows cannot divide over 4 devices: refused, not unsharded
            ClientArena.create(8, {{}}).shard_rows(
                Mesh(np.array(jax.devices()[:4]), ('clients',)))
            out['uneven_refused'] = False
        except ValueError:
            out['uneven_refused'] = True
        print(json.dumps(out))
    """)
    proc = subprocess.run([sys.executable, "-c", body], capture_output=True,
                          text=True, env=env, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert out["arena_rows_per_device"] == [2, 2, 2, 2]
    assert out["chunk_clients_per_device"] == [1, 1, 1, 1]
    assert out["uneven_refused"]


@pytest.mark.parametrize("where", ["checkout", "alone"])
def test_script_refuses_without_a_tpu(tmp_path, where):
    script = SCRIPT
    if where == "alone":   # a directory with the script and nothing else
        script = str(tmp_path / "chip_smoke.py")
        with open(SCRIPT) as src, open(script, "w") as dst:
            dst.write(src.read())
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run([sys.executable, script], capture_output=True,
                          text=True, env=env, cwd=os.path.dirname(script),
                          timeout=300)
    assert proc.returncode != 0
    assert '"ok": true' not in proc.stdout


def test_compile_cache_follows_the_environment(tmp_path):
    code = ("import jax, jax.numpy as jnp\n"
            "from repro.launch.compile_cache import enable_compile_cache\n"
            "print(enable_compile_cache())\n"
            "print(jax.config.jax_compilation_cache_dir)\n"
            "jax.jit(lambda x: jnp.sin(x) @ x.T)(jnp.ones((8, 8)))"
            ".block_until_ready()\n")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.path.join(REPO, "src"),
               JAX_COMPILATION_CACHE_DIR=str(tmp_path),
               JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0")
    proc = subprocess.run([sys.executable, "-c", code], capture_output=True,
                          text=True, env=env, timeout=300)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert proc.stdout.split() == [str(tmp_path)] * 2
    assert os.listdir(tmp_path)   # the compile was written there

    # unset: a fixed directory in the checkout (reported, not written)
    env.pop("JAX_COMPILATION_CACHE_DIR")
    code = ("import jax\n"
            "from repro.launch.compile_cache import enable_compile_cache\n"
            "print(enable_compile_cache())\n"
            "print(jax.config.jax_compilation_cache_dir)\n")
    proc = subprocess.run([sys.executable, "-c", code], capture_output=True,
                          text=True, env=env, timeout=300)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert proc.stdout.split() == [os.path.join(REPO, ".jax_cache")] * 2


def test_fleet_children_refused_after_jax_import():
    import jax  # noqa: F401 - this process has JAX, as a benchmark would

    fl_streaming = _load("fl_streaming",
                         os.path.join(REPO, "benchmarks", "fl_streaming.py"))
    with pytest.raises(RuntimeError, match="before this process imports"):
        fl_streaming.fleet_section((100,))


def test_benchmark_csv_runs_fleet_sweep_before_jax():
    # the stand-ins record whether JAX was loaded when the sweep ran, and
    # what the fl_streaming group was handed
    code = textwrap.dedent("""
        import sys
        from benchmarks import fl_streaming, run
        fl_streaming.fleet_section = lambda: {"jax": "jax" in sys.modules}
        fl_streaming.csv_rows = lambda fleet=None: [
            ("fleet_seen", 0.0, repr(fleet))]
        sys.argv = ["run", "--only", "stream", "--skip-roofline"]
        run.main()
    """)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.path.join(REPO, "src"))
    proc = subprocess.run([sys.executable, "-c", code], capture_output=True,
                          text=True, env=env, cwd=REPO, timeout=300)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "fleet_seen,0.0,{'jax': False}" in proc.stdout.splitlines()


def test_train_runs_on_the_devices_it_is_given(monkeypatch):
    import jax

    from repro.launch import train

    # leave this process's compile cache setting alone
    monkeypatch.setattr(train, "enable_compile_cache", lambda: None)
    # one more than this process sees (a module imported earlier may
    # have asked XLA for many host devices)
    monkeypatch.setattr(sys, "argv", [
        "train", "--mode", "fl", "--engine", "streaming",
        "--devices", str(len(jax.devices()) + 1)])
    with pytest.raises(SystemExit, match="visible"):
        train.main()
