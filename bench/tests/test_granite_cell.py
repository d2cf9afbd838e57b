"""The granite4_h_micro cell, on the CPU.

Hand counts of the granite configuration's FLOPs and kernel calls; the
cell resolves through the manifest; the benchmark's weights have
the program's layout at the published widths; a tiny granite cell runs
two streaming ``FLServer`` rounds (arena, int8 uplink, fused kernels in
interpret mode) that the plain reference (``reference.py``) finds
correct, and a planted state-unchanged fault that it does not; its
``train_tokens`` counts are exact.
"""
import sys
from pathlib import Path

import jax
import pytest

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(ROOT / "src"))

import calibrate  # noqa: E402
import harness  # noqa: E402
import manifest  # noqa: E402

GRANITE = "granite4_h_micro.silo4"


def granite():
    return manifest.resolve(ROOT, GRANITE).config


def tiny_granite():
    """The granite cell at a size the CPU runs in seconds: reduced
    widths (the four-layer pattern "mmma"), 11 positions, the traffic's
    cohort, chunks, codecs and limits as they are."""
    from repro.core import rank_policy

    cell = manifest.resolve(ROOT, GRANITE)

    def r(m, n):
        return rank_policy.matrix_rank_for_gamma(m, n, cell.spec["gamma"])

    cell.spec = dict(
        cell.spec, hidden_size=64, num_attention_heads=4,
        num_key_value_heads=1, intermediate_size=128, vocab_size=256,
        mamba_d_state=16, mamba_d_head=16, mamba_n_heads=8,
        mamba_chunk_size=8, layers=4,
        layer_types=["mamba", "mamba", "mamba", "attention"],
        min_dim_for_factorization=8, seq_len=11, attn_chunk=8,
        logit_chunk=8,
        fedpara_ranks={"in_proj": r(64, 296), "out_proj": r(128, 64),
                       "gate": r(64, 128), "up": r(64, 128),
                       "down": r(128, 64), "q": r(64, 64), "k": r(64, 16),
                       "v": r(64, 16), "o": r(64, 64)})
    return cell


@pytest.fixture
def cache_dir(tmp_path, monkeypatch):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "cache"))


# ------------------------------------------------------------ manifest

def test_both_new_cells_resolve():
    g = manifest.resolve(ROOT, GRANITE)
    assert (g.chips, g.config_name, g.traffic_name) == (1, "granite4_h_micro",
                                                        "granite_silo4")
    assert (g.traffic["cohort"], g.traffic["client_chunk"],
            g.traffic["reference_block"]) == (4, 2, 1)
    assert "train_tokens_per_s" in [m.name for m in g.per_layer]
    assert "fedpara_kernel_roofline" in [m.name for m in g.per_layer]
    # the granite limits include the number that fails the bf16 control
    assert set(g.limits) >= {"update_gap_median", "change_gap_median",
                             "first_loss_gap"}
    # the LSTM cells report no token counter
    c = manifest.resolve(ROOT, "lstm_shakespeare.c16")
    assert "train_tokens_per_s" not in [m.name for m in c.per_layer]


def test_config_file_keeps_the_published_numbers():
    spec = manifest.resolve(ROOT, GRANITE).spec
    assert spec["num_hidden_layers"] == 40 and spec["layers"] == 10
    assert spec["reduced"] == ["layers", "samples_per_client"]
    assert spec["layer_types"][:10].count("attention") == 1
    assert spec["layer_types"].count("attention") == 4
    assert set(spec["reduced"]) <= set(spec["assumed"])


def test_granite_limits_pass_sound_chip_readings_and_fail_the_control():
    """The limits against the chip's calibration readings (seed
    2718281829): the program's first rounds pass, the reference computed
    in bfloat16 in their place fails, by the diff median and the first
    loss gap while the gap medians pass it."""
    import check

    limits = manifest.resolve(ROOT, GRANITE).limits
    exact = {"cohort_mismatch": 0, "wire_bytes_off": 0, "nonfinite_losses": 0}
    sound = dict(exact, update_gap_median=0.018019238935474365,
                 change_gap_median=0.01247444191293722,
                 update_diff_median=0.12410831408590023,
                 first_loss_gap=1.5148604594588375e-06)
    control = dict(exact, update_gap_median=0.021135111453062422,
                   change_gap_median=0.03608035347834914,
                   update_diff_median=0.4801947766249007,
                   first_loss_gap=0.0009387777030884705)
    assert check.judge(sound, limits)[0]
    correct, checks = check.judge(control, limits)
    assert not correct
    failed = {n for n, c in checks.items() if c["value"] > c["limit"]}
    assert failed == {"update_diff_median", "first_loss_gap"}


# ------------------------------------------------------------- counts

def test_flops_per_sample_by_hand():
    s, d, v = 4096, 2048, 100352
    mamba = 2 * (2048 * 8512 + 4096 * 2048 + 3 * 2048 * 8192)
    attention = 2 * (2 * 2048 * 2048 + 2 * 2048 * 512 + 3 * 2048 * 8192)
    per_pos = 9 * mamba + attention + 2 * d * v
    per_pos += 2 * 2 * (s + 1) / 2 * 32 * 64            # scores and values
    per_pos += 9 * (2 * 257 / 2 * (128 + 4096) + 4 * 4096 * 128)
    assert granite().flops_per_sample() == 3 * s * per_pos
    # a round: 4 silos x 2 documents, about 0.19 PFLOP
    assert 1.8e14 < 8 * granite().flops_per_sample() < 2.0e14


def test_compose_flops_by_hand():
    def one(m, n, r):
        return 12 * m * n * r + 3 * m * n

    mlp = 2 * one(2048, 8192, 123) + one(8192, 2048, 123)
    mamba = one(2048, 8512, 124) + one(4096, 2048, 110) + mlp
    attention = (2 * one(2048, 2048, 93) + 2 * one(2048, 512, 41) + mlp)
    assert granite().compose_flops_per_step() == 9 * mamba + attention


def test_kernel_calls_by_hand():
    calls = granite().kernel_calls(1, 2)
    by = {(c["matrix"], c["kind"]): c for c in calls}
    assert len(calls) == 4 * 9            # nine matrices a layer, four kinds
    t, m, n, r = 4096, 2048, 8512, 124    # in_proj, one per Mamba-2 layer
    fwd = by[("w_in.2048x8512", "fwd")]
    assert fwd["count"] == 9
    assert fwd["flops"] == 2 * (2 * t * m * n + 4 * m * n * r + m * n)
    assert fwd["bytes"] == 2 * (2 * t * (m + n) + 4 * 2 * (m + n) * r)
    dy = by[("w_in.2048x8512", "dfactors_y")]
    assert dy["flops"] == 2 * (2 * t * m * n + 4 * m * n * r + m * n
                               + 2 * m * n + 4 * m * n * r)
    assert dy["bytes"] == 2 * (2 * t * (m + n) + 4 * 2 * (m + n) * r
                               + 4 * 2 * n * r)
    # the MLP in all 10 layers, attention's in the one attention layer
    assert by[("w_gate.2048x8192", "dx")]["count"] == 10
    assert by[("wk.2048x512", "dx")]["count"] == 1
    assert by[("w_down.8192x2048", "fwd")]["count"] == 10
    assert sum(c["count"] for c in calls) == 4 * (9 * 5 + 7)


def test_benchmark_weights_have_the_programs_layout():
    from repro.configs import ParamCfg, get_arch
    from repro.nn.transformer import build_model

    cell = manifest.resolve(ROOT, GRANITE)
    ours = jax.eval_shape(lambda: cell.config.init_params(
        jax.random.PRNGKey(0), cell.spec))
    model = build_model(get_arch("granite-4.0-h-micro").with_(
        n_layers=10, param=ParamCfg(gamma=0.1)))
    theirs = jax.eval_shape(model.init_params, jax.random.PRNGKey(0))
    assert jax.tree.structure(ours) == jax.tree.structure(theirs)
    assert all(jax.tree.leaves(jax.tree.map(
        lambda a, b: a.shape == b.shape and a.dtype == b.dtype, ours, theirs)))
    sizes = [x.size for x in jax.tree.leaves(ours)]
    assert sum(sizes) == 113_527_232 + 205_520_896


# ------------------------------------------------------- tiny rounds

def test_tiny_granite_rounds_are_correct_and_count_their_tokens(cache_dir):
    cell = tiny_granite()
    out = harness.execute(cell, jax.devices()[:1], {}, 2 ** 31 + 3, 0.1,
                          False, 0.0)
    assert out["correct"], out["checks"]
    setup = harness.build(cell, 7, jax.devices()[:1])
    spec = cell.spec
    steps = spec["samples_per_client"] // spec["batch"] * spec["epochs"]
    for rec in setup.records:
        assert rec["train_tokens"] == (cell.traffic["cohort"] * steps
                                       * spec["batch"] * spec["seq_len"])


def test_tiny_granite_with_the_model_unchanged_is_not_correct(cache_dir):
    cell = tiny_granite()
    with calibrate.planted("stale_state", cell):
        out = harness.execute(cell, jax.devices()[:1], {}, 31, 0.1, False,
                              0.0)
    assert not out["correct"], out["checks"]
    assert out["checks"]["update_gap_median"]["value"] > 0.9


def test_train_tokens_per_s_reads_the_traced_rounds():
    import xplane

    cell = manifest.resolve(ROOT, GRANITE)
    metric = next(m for m in cell.per_layer if m.name == "train_tokens_per_s")
    span = xplane.Event("run_round", 0.0, 4e9)
    trace = xplane.Trace(host=[span], spans=[span], rounds=2)
    w = harness.Window(rounds=3, trace=trace, records=[
        {"train_tokens": 32768}, {"train_tokens": 32768}, {"train_tokens": 1}])
    ctx = harness.Context(cell, 0.0, w, {}, [type("D", (), {"id": 0})()])
    assert metric.module.compute(ctx) == 2 * 32768 / 4.0
    # a program without the counter gives no reading, and no error
    w.records = [{"host_batch_bytes": 8}] * 3
    assert metric.module.compute(ctx) is None
