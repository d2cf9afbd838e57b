"""The system under test, as the benchmark drives it: one ``FLServer``
per run, built through the repository's public API from a cell's
configuration and traffic files. The only module of the harness that
imports the program, apart from each configuration's ``program_loss``."""
from __future__ import annotations

import numpy as np


def make_server(cell, loss_fn, params0, data: dict, partitions: list,
                server_seed: int, mesh=None):
    """The federation of ``cell``: FedAvg over the configuration's fleet,
    with the traffic file's cohort, engine, chunk, codecs, state store
    and data stream, and no held-out evaluation."""
    from repro.fl import ClientConfig, FLServer, ServerConfig, make_strategy

    spec, traffic = cell.spec, cell.traffic
    return FLServer(
        loss_fn, params0, data, partitions, make_strategy("fedavg"),
        ClientConfig(lr=spec["lr"], batch=spec["batch"],
                     epochs=spec["epochs"]),
        ServerConfig(clients=spec["clients"],
                     participation=traffic["cohort"] / spec["clients"],
                     lr_decay=spec["lr_decay"],
                     engine=traffic["engine"],
                     client_chunk=traffic["client_chunk"],
                     uplink_codec=traffic["uplink_codec"],
                     downlink_codec=traffic["downlink_codec"],
                     state_store=traffic["state_store"],
                     data_stream=traffic["data_stream"],
                     seed=server_seed),
        mesh=mesh)


def client_mesh(devices):
    """A ``("clients",)`` mesh over ``devices``, or ``None`` for one."""
    if len(devices) == 1:
        return None
    from jax.sharding import Mesh

    return Mesh(np.array(devices), ("clients",))
