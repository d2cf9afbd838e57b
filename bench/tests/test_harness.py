"""The benchmark harness on the CPU, at sizes a test run can hold.

What a chip run needs is checked here without one: every cell of
``BENCHMARK.json`` resolves to its files, the trace reducers and the
FLOP counts give hand-computed answers, the benchmark's own byte count
equals the program's, the reference and the control behave, a run with
a planted fault comes out not correct, and the command refuses to run
without a TPU.
"""
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(ROOT / "src"))

import calibrate  # noqa: E402
import check  # noqa: E402
import harness  # noqa: E402
import manifest  # noqa: E402
import reference  # noqa: E402
import xplane  # noqa: E402

MANIFEST = manifest.load(ROOT)
CELLS = [w["name"] for w in MANIFEST["workloads"]]
PEAKS = json.loads((BENCH / "peaks.json").read_text())["devices"]["TPU v5 lite"]


def tiny(cell_name: str):
    """The cell at a size the CPU runs in seconds: every width and the
    traffic's shape cut, the code paths and the limits as they are."""
    from repro.core import rank_policy

    cell = manifest.resolve(ROOT, cell_name)
    if cell.config_name == "lstm_shakespeare":
        cell.spec = dict(cell.spec, hidden=32, gate_ranks=[[3, 4], [4, 4]],
                         seq_len=12, clients=15, samples_per_client=64,
                         batch=8)
    else:
        cell.spec = dict(cell.spec, plan=[16, "M", 32, "M"], fc_dims=[32],
                         clients=9, samples_per_client=40, batch=8, epochs=1,
                         conv_ranks=[0, rank_policy.conv_rank_for_gamma(
                             32, 16, 3, 3, cell.spec["gamma"])])
    cell.traffic = dict(cell.traffic, cohort=4, client_chunk=4,
                        reference_block=2)
    return cell


@pytest.fixture
def cache_dir(tmp_path, monkeypatch):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "cache"))


# ------------------------------------------------------------ manifest

@pytest.mark.parametrize("name", CELLS)
def test_every_cell_resolves_to_its_files(name):
    cell = manifest.resolve(ROOT, name)
    assert cell.spec["name"] == cell.config_name
    for key in ("init_params", "make_data", "program_loss",
                "reference_loss", "flops_per_sample", "kernel_calls"):
        assert callable(getattr(cell.config, key))
    e2e = [m.name for m in cell.end_to_end]
    assert "setup_s" in e2e and len(e2e) >= 2
    assert cell.per_layer
    for m in cell.end_to_end + cell.per_layer:
        assert callable(m.module.compute)
    assert set(cell.limits) >= {"cohort_mismatch", "wire_bytes_off",
                                "nonfinite_losses"}
    assert cell.traffic["devices"] == cell.chips


def test_a_cell_added_as_files_is_found_without_edits(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    before = {p: p.read_bytes() for p in (tmp_path / "bench").rglob("*")
              if p.is_file()}
    traffic = json.loads((BENCH / "traffic" / "shakespeare_c16.json").read_text())
    (tmp_path / "bench" / "traffic" / "shakespeare_c8.json").write_text(
        json.dumps(dict(traffic, cohort=8, client_chunk=8)))
    shutil.copy(BENCH / "limits" / "lstm_shakespeare.c16.json",
                tmp_path / "bench" / "limits" / "lstm_shakespeare.c8.json")
    m = json.loads((tmp_path / "BENCHMARK.json").read_text())
    m["workloads"].append({"name": "lstm_shakespeare.c8",
                           "config": "lstm_shakespeare",
                           "traffic": "shakespeare_c8", "chips": 1,
                           "why": "a smaller cohort"})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(m))
    cell = manifest.resolve(tmp_path, "lstm_shakespeare.c8")
    assert cell.traffic["cohort"] == 8
    # a metric limited to other cells by its "workloads" stays out
    assert [x.name for x in cell.per_layer] == ["device_idle_share",
                                                "round_mfu"]
    assert all(p.read_bytes() == b for p, b in before.items())


# --------------------------------------------------------- reductions

def ev(name, start, dur):
    return xplane.Event(name, start, dur)


class FakeCtx(harness.Context):
    def __init__(self, cell, trace, devices=1):
        w = harness.Window(rounds=trace.rounds, trace=trace)
        super().__init__(cell, 0.0, w, PEAKS,
                         [type("D", (), {"id": i})() for i in range(devices)])


def traced(events, spans, rounds=1):
    return xplane.Trace(devices={0: events}, host=spans, spans=spans,
                        rounds=rounds)


def test_idle_share_is_one_minus_the_union_of_op_intervals():
    spans = [ev("run_round", 0, 100)]
    ops = [ev("a", 0, 10), ev("b", 5, 15), ev("c", 30, 10), ev("d", 95, 20)]
    tr = traced(ops, spans)
    assert xplane.busy_ns(ops, 0, 100) == 20 + 10 + 5
    assert xplane.gaps(ops, 0, 100) == [(20, 30), (40, 95)]
    cell = manifest.resolve(ROOT, "lstm_shakespeare.c16")
    idle = manifest.load_module(BENCH / "metrics" / "device_idle_share.py", "m1")
    assert idle.compute(FakeCtx(cell, tr)) == pytest.approx(65.0)


def test_leaves_drop_the_ops_that_enclose_others():
    ops = [ev("while.1", 0, 100), ev("fusion.2", 0, 10), ev("fusion.3", 20, 30)]
    assert [e.name for e in xplane.leaves(ops)] == ["fusion.2", "fusion.3"]
    assert [e.base for e in ops] == ["while", "fusion", "fusion"]
    assert xplane.op_name("%fedpara_dx.37 = f32[16,32,256]{2,1,0} "
                          "custom-call(f32[16,32,1024] %x)") == "fedpara_dx.37"


def test_kernel_roofline_sums_the_named_kernels_time():
    cell = manifest.resolve(ROOT, "lstm_shakespeare.c16")
    ops = [ev("fedpara_matmul.3", 0, 1e6), ev("fedpara_dx.4", 2e6, 1e6),
           ev("fedpara_dx_factors.5", 4e6, 1e6),
           ev("fedpara_dy_factors.6", 5e6, 1e6), ev("fusion.7", 7e6, 5e6),
           ev("fedpara_dx_fusion.8", 12e6, 1e6)]
    tr = traced(ops, [ev("run_round", 0, 1e7)])
    mod = manifest.load_module(BENCH / "metrics" / "fedpara_kernel_roofline.py",
                               "m2")
    spec = cell.spec
    steps = spec["samples_per_client"] // spec["batch"] * spec["epochs"]
    least = sum(c["count"] * steps * max(c["flops"] / PEAKS["bf16_flops"],
                                         c["bytes"] / PEAKS["hbm_bytes_per_s"])
                for c in cell.config.kernel_calls(spec["batch"], 16, spec))
    assert mod.compute(FakeCtx(cell, tr)) == pytest.approx(100 * least / 4e-3)
    vgg = manifest.resolve(ROOT, "vgg16_cifar10.c16")
    assert mod.compute(FakeCtx(vgg, tr)) is None


def test_breakdown_names_each_gap_by_the_host_span_it_falls_in():
    cell = manifest.resolve(ROOT, "lstm_shakespeare.c16")
    spans = [ev("run_round", 0, 100), ev("between_rounds", 100, 20)]
    host = sorted(spans + [ev("PjitFunction(_round_program)", 10, 5)],
                  key=lambda e: e.start)
    tr = xplane.Trace(devices={0: [ev("a", 20, 70), ev("b", 115, 5)]},
                      host=host, spans=spans, rounds=1)
    out = harness.breakdown(FakeCtx(cell, tr))
    assert out["device_ops"] == [["a", pytest.approx(70e-9)],
                                 ["b", pytest.approx(5e-9)]]
    assert out["idle_gaps"] == [["between_rounds", pytest.approx(25e-9)],
                                ["run_round>PjitFunction(_round_program)",
                                 pytest.approx(20e-9)]]


# -------------------------------------------------------------- counts

def test_vgg16_forward_is_313m_macs():
    vgg = manifest.resolve(ROOT, "vgg16_cifar10.c16").config
    by_hand = (32 * 32 * 64 * (3 + 64) * 9 + 16 * 16 * 128 * (64 + 128) * 9
               + 8 * 8 * 256 * (128 + 256 + 256) * 9
               + 4 * 4 * 512 * (256 + 512 + 512) * 9
               + 2 * 2 * 512 * 512 * 3 * 9 + 512 * 512 * 2 + 512 * 10)
    assert vgg.forward_macs() == by_hand
    assert 313e6 < by_hand < 314e6


def test_lstm_flops_per_sequence_by_hand():
    lstm = manifest.resolve(ROOT, "lstm_shakespeare.c16").config
    per_position = 2 * (8 + 256 + 256 + 256) * 1024 + 2 * 256 * 80
    assert lstm.flops_per_sample() == 3 * 79 * per_position
    # the round: 16 clients x 20 steps x 32 sequences, about 4 TFLOP
    assert 3.9e12 < 16 * 640 * lstm.flops_per_sample() < 4.0e12


@pytest.mark.parametrize("config", ["lstm_shakespeare", "vgg16_cifar10"])
def test_benchmark_weights_have_the_programs_layout(config):
    from repro.nn.recurrent import LSTMConfig, init_lstm
    from repro.nn.vision import VGGConfig, init_vgg

    cell = next(manifest.resolve(ROOT, w["name"]) for w in MANIFEST["workloads"]
                if w["config"] == config)
    ours = jax.eval_shape(lambda: cell.config.init_params(
        jax.random.PRNGKey(0), cell.spec))
    theirs = jax.eval_shape(
        (lambda: init_lstm(jax.random.PRNGKey(0), LSTMConfig()))
        if config == "lstm_shakespeare" else
        (lambda: init_vgg(jax.random.PRNGKey(0), VGGConfig())))
    assert jax.tree.structure(ours) == jax.tree.structure(theirs)
    assert all(jax.tree.leaves(jax.tree.map(
        lambda a, b: a.shape == b.shape and a.dtype == b.dtype, ours, theirs)))


def test_seeds_beyond_32_bits_give_distinct_32_bit_streams():
    a, b = harness.seeds(2 ** 31 + 7), harness.seeds(2 ** 33 + 7)
    assert a != b and a == harness.seeds(2 ** 31 + 7)
    assert all(0 <= v < 2 ** 32 for v in (*a.values(), *b.values()))


# ------------------------------------------------------ output check

def test_own_byte_count_equals_the_programs_comm_log(cache_dir):
    cell = tiny("lstm_shakespeare.c16")
    cell.traffic["check_rounds"] = 1
    setup = harness.build(cell, 5, jax.devices()[:1])
    up, down = check.wire_bytes_per_client(setup.params0, "int8")
    log = setup.server.comm_log
    assert (log.up_bytes, log.down_bytes) == (4 * up, 4 * down)
    assert harness.check_bytes(setup, setup.records) == 0


def test_reference_imports_nothing_of_the_program():
    code = f"""
import sys
sys.path.insert(0, {str(BENCH)!r})
import jax, manifest, reference
cell = manifest.resolve(__import__("pathlib").Path({str(ROOT)!r}), "lstm_shakespeare.c16")
spec = dict(cell.spec, hidden=16, gate_ranks=[[2, 2], [2, 2]], seq_len=6,
            clients=6, samples_per_client=8, batch=4)
data = {{k: jax.numpy.asarray(v) for k, v in cell.config.make_data(jax.random.PRNGKey(0), spec).items()}}
params = cell.config.init_params(jax.random.PRNGKey(1), spec)
parts = cell.config.partition(48, 6, 2)
out = reference.run(lambda p, b: cell.config.reference_loss(p, b, spec), spec,
                    dict(cell.traffic, cohort=2), params,
                    {{k: __import__("numpy").asarray(v) for k, v in data.items()}},
                    parts, 3, 1, block=2)
assert not [m for m in sys.modules if m == "repro" or m.startswith("repro.")]
print("ok", out.losses)
"""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, env=env, timeout=600)
    assert r.returncode == 0, r.stderr[-2000:]
    assert r.stdout.startswith("ok")


def test_a_sound_run_is_correct(cache_dir):
    cell = tiny("lstm_shakespeare.c16")
    out = harness.execute(cell, jax.devices()[:1], PEAKS, 2 ** 31 + 11, 0.1,
                          False, 0.0)
    assert out["correct"], out["checks"]
    assert out["failed"] == 0 and out["attempted"] >= 4
    assert set(out["metrics"]) == {"round_s", "wire_mb_per_round", "setup_s"}
    assert list(out)[-1] == "checks"


def test_the_bfloat16_control_reads_far_above_the_program(cache_dir):
    """On the CPU the program computes in float32 like the reference, so
    the control's differences stand far above its own; on the chip no
    compared number separates them yet (PERF.md, output check)."""
    cell = tiny("lstm_shakespeare.c16")
    setup = harness.build(cell, 21, jax.devices()[:1])
    setup.server = None
    numbers, ref = harness.reference_numbers(setup)
    assert check.judge(dict(numbers, wire_bytes_off=0, nonfinite_losses=0),
                       cell.limits)[0]
    ctrl = harness.run_reference(setup, dtype=jnp.bfloat16,
                                 precision="default")
    control = check.compare(setup.params0, ctrl.params, ctrl.losses,
                            ctrl.cohorts, ref)
    for name in ("update_diff_median", "change_diff_median"):
        assert control[name] > 100 * numbers[name], (name, control, numbers)


@pytest.mark.parametrize("cell_name", ["lstm_shakespeare.c16",
                                       "vgg16_cifar10.c16"])
def test_a_round_that_leaves_the_model_unchanged_is_not_correct(cache_dir,
                                                                cell_name):
    cell = tiny(cell_name)
    with calibrate.planted("stale_state", cell):
        out = harness.execute(cell, jax.devices()[:1], PEAKS, 31, 0.1,
                              False, 0.0)
    assert not out["correct"], out["checks"]
    assert out["checks"]["update_gap_median"]["value"] > 0.9


@pytest.mark.parametrize("cell_name", ["lstm_shakespeare.c16",
                                       "vgg16_cifar10.c16"])
def test_local_steps_on_half_the_batch_read_far_from_the_reference(
        cache_dir, cell_name):
    """The half-batch fault, planted underneath the harness, moves the
    logged differences far above a sound run's (CPU sizes)."""
    cell = tiny(cell_name)
    with calibrate.planted("half_batch", cell):
        setup = harness.build(cell, 2 ** 32 + 5, jax.devices()[:1])
    setup.server = None
    numbers, _ = harness.reference_numbers(setup)
    assert numbers["update_diff_median"] > 0.1, numbers


# ---------------------------------------------------------- no chip

def _run_cmd(cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "lstm_shakespeare.c16",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=cwd, capture_output=True, text=True, env=env, timeout=300)


def test_without_a_tpu_the_command_exits_nonzero_and_prints_nothing():
    r = _run_cmd(ROOT)
    assert r.returncode != 0
    assert r.stdout.strip() == ""
    assert "no TPU" in r.stderr


def test_with_only_the_benchmark_files_the_command_exits_nonzero(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    r = _run_cmd(tmp_path)
    assert r.returncode != 0
    assert r.stdout.strip() == ""
