"""How the benchmark builds the program's objects from a configuration file."""

from __future__ import annotations

from pathlib import Path

from bench import state


def codec(config: dict):
    """``(ZipNNConfig, CodecOptions)`` of the file's ``codec`` entry."""
    from repro.core import zipnn

    c = config["codec"]
    return (zipnn.ZipNNConfig(backend=c["coder"], chunk_param_bytes=c["chunk_param_bytes"]),
            zipnn.CodecOptions(backend=c["backend"]))


def manager(config: dict, directory: Path):
    """A ``CheckpointManager`` that saves as the configuration states:
    blocking, its ``checkpoint`` settings or the program's defaults."""
    from repro.checkpoint import CheckpointConfig, CheckpointManager

    zcfg, opts = codec(config)
    ck = dict(config.get("checkpoint", {}))
    return CheckpointManager(CheckpointConfig(str(directory), async_save=False, zipnn=zcfg,
                                              options=opts, **ck))


def model(run):
    from repro.models import build_model

    cfg = state.model_config(run.config, run.overrides.get("model"))
    return cfg, build_model(cfg)
