"""The granite cell's round program, and the LSTM round program on the
four-chip client mesh, compile for a TPU v5e and fit a chip's memory.

As ``test_cells_compile.py`` (whose ``round_program_args`` builds the
shapes): nothing runs, the kernels take their compiled branch, and
``memory_analysis()`` has to fit 16 GiB. The four-chip case is the
paper's LSTM with a cohort of 64 in one chunk on a ``v5e:2x2``
``("clients",)`` mesh, 16 clients a chip: the chunk-stacked inputs are
split as the engine's ``chunk_sharding`` places them, the global model
replicated, and the per-chip folds are summed across the chips.

The topology is described inside a fixture, never while a module is
imported: only one process at a time may load the TPU library, and the
test workers import every test file.
"""
import os
import sys

import jax
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
for path in (HERE, BENCH, os.path.join(os.path.dirname(BENCH), "src")):
    if path not in sys.path:
        sys.path.insert(0, path)

import harness  # noqa: E402
import manifest  # noqa: E402
from test_cells_compile import CHIP_BYTES, round_program_args  # noqa: E402


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        yield topologies.get_topology_desc(platform="tpu",
                                           topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure to describe it
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        cc.reset_cache()


@pytest.fixture
def compiled_kernels(monkeypatch):
    # code that picks interpret mode by asking for the backend takes
    # the chip's branch, as it does on the chip
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")


def _fits(compiled) -> int:
    mem = compiled.memory_analysis()
    total = (mem.temp_size_in_bytes + mem.argument_size_in_bytes
             + mem.output_size_in_bytes - mem.alias_size_in_bytes)
    assert "tpu_custom_call" in compiled.as_text()
    assert total < CHIP_BYTES, total
    return total


def test_granite_round_program_compiles_and_fits(topo, compiled_kernels):
    cell = manifest.resolve(harness.ROOT, "granite4_h_micro.silo4")
    program, args = round_program_args(cell,
                                       SingleDeviceSharding(topo.devices[0]))
    print("granite4_h_micro.silo4:", _fits(program.lower(*args).compile()), "B")


def test_four_chip_lstm_round_program_compiles_and_fits(topo,
                                                       compiled_kernels):
    from repro.fl import ClientConfig, make_strategy
    from repro.fl.codecs import make_codec
    from repro.fl.stream_engine import StreamingRound, chunk_layout

    mesh = Mesh(np.array(topo.devices), ("clients",))
    cell = manifest.resolve(harness.ROOT, "lstm_shakespeare.c16")
    cell.traffic = dict(cell.traffic, cohort=64, client_chunk=64, devices=4)
    spec, traffic = cell.spec, cell.traffic
    engine = StreamingRound(
        loss_fn=cell.config.program_loss(spec),
        strategy=make_strategy("fedavg"),
        client_cfg=ClientConfig(lr=spec["lr"], batch=spec["batch"],
                                epochs=spec["epochs"]),
        uplink_codec=make_codec(traffic["uplink_codec"]),
        chunk=traffic["client_chunk"], mesh=mesh)
    chunk, n_chunks, _ = chunk_layout(traffic["cohort"],
                                      traffic["client_chunk"])
    split = engine.chunk_sharding(chunk)
    assert split is not None
    rep = NamedSharding(mesh, P())

    def place(x):
        # the engine's contract: the chunk-stacked (n_chunks, chunk, ...)
        # inputs split over the mesh, everything else replicated
        stacked = tuple(x.shape[:2]) == (n_chunks, chunk)
        return jax.ShapeDtypeStruct(x.shape, x.dtype,
                                    sharding=split if stacked else rep)

    _, args = round_program_args(cell, rep)
    args = jax.tree.map(place, args)
    compiled = engine._program.lower(*args).compile()
    print("64 clients on v5e:2x2:", _fits(compiled), "B a chip")
    assert "all-reduce" in compiled.as_text()
