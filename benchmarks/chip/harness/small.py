"""A cell at a size a CPU test run can hold: the repo's smoke widths of
the configuration, four clients, a few hundred samples.  Only the
harness's own tests use it (``run_cell(..., smoke=True, edit=edit)``)."""

from __future__ import annotations

import dataclasses


# a token model's sequences at a CPU size
SMOKE_SEQ_LEN = 32


def _fields(cfg):
    """A config dataclass as the ``model`` block writes it: tuples as
    lists, dataclass sub-configs as objects."""
    out = {}
    for f in dataclasses.fields(cfg):
        v = getattr(cfg, f.name)
        if dataclasses.is_dataclass(v):
            v = _fields(v)
        elif isinstance(v, tuple):
            v = list(v)
        out[f.name] = v
    return out


def smoke_model(arch):
    """The configuration file's ``model`` block for the repo's smoke
    config of ``arch``."""
    from repro.configs import registry

    out = _fields(registry.get_smoke_config(arch))
    del out["name"]
    return out


def edit(cfg, cell):
    cfg["model"] = smoke_model(cfg["arch"])
    cell["fed"].update(num_clients=4, clients_per_round=2, local_steps=2,
                       device_batch_size=8, server_batch_size=16)
    cell["data"].update(train_samples=256, eval_samples=64)
    if "seq_len" in cell["data"]:
        cell["data"]["seq_len"] = SMOKE_SEQ_LEN
    if cell.get("server_pool") == "streamed":
        # as at full size, the pool streams from the host
        cell["device_pool_budget_mb"] = 0
