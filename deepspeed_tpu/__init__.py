"""deepspeed_tpu — a TPU-native distributed training & inference framework
with DeepSpeed-class capabilities (reference: dc3671/DeepSpeed), built on
JAX/XLA/Pallas/pjit.

Public surface mirrors the reference's ``deepspeed/__init__.py``:
``initialize`` (:64), ``init_inference`` (:269), ``comm`` as the collective
module, plus the accelerator registry.
"""

__version__ = "0.1.0"

from . import comm  # noqa: F401
from .accelerator import get_accelerator, set_accelerator  # noqa: F401
from .runtime.config import DeepSpeedTpuConfig, load_config  # noqa: F401


def initialize(args=None,
               model=None,
               optimizer=None,
               model_parameters=None,
               training_data=None,
               lr_scheduler=None,
               distributed_port=29500,
               mesh=None,
               dist_init_required=None,
               collate_fn=None,
               config=None,
               config_params=None,
               rng=None):
    """Create the training engine (reference deepspeed/__init__.py:64).

    ``model`` is a model description (see deepspeed_tpu.models) or any object
    exposing ``init(rng, batch) -> params`` and ``apply(params, batch) ->
    loss``; returns ``(engine, optimizer, dataloader, lr_scheduler)`` for
    API parity — the engine owns all four.
    """
    from .runtime.engine import DeepSpeedTpuEngine

    config = config if config is not None else config_params

    # ZeRO-Infinity parameter streaming: params on NVMe/host DRAM, layer
    # groups paged through HBM (runtime/zero_infinity.py). Selected — like
    # the reference's swap-tensor path — by offload_param in the config.
    cfg_obj = load_config(config)
    op = cfg_obj.zero_optimization.offload_param
    if op is not None and str(op.device.value) in ("cpu", "nvme"):
        from .runtime.zero_infinity import ZeroInfinityEngine

        if cfg_obj.hybrid_engine.enabled:
            raise ValueError(
                "hybrid_engine is not supported with offload_param "
                "(ZeRO-Infinity streaming owns the parameter lifecycle)")

        unsupported = {"optimizer": optimizer, "training_data": training_data,
                       "lr_scheduler": lr_scheduler,
                       "model_parameters": model_parameters}
        bad = [k for k, v in unsupported.items() if v is not None]
        if bad:
            raise ValueError(
                f"offload_param (ZeRO-Infinity streaming) does not accept "
                f"{bad}; the streaming engine owns its optimizer and data "
                "path (runtime/zero_infinity.py)")
        if cfg_obj.zero_optimization.stage < 3:
            raise ValueError("offload_param requires zero_optimization.stage=3")
        if isinstance(model, str):
            from .models import build_model

            model = build_model(model)
        # Mesh composition: streaming runs under fsdp×data sharding (the
        # reference's NVMe swap runs under ZeRO-3 partitioning the same
        # way — stage3.py:72); other axes don't compose with streaming.
        from .parallel import topology as _topo

        mesh = None
        if "mesh" in cfg_obj.model_fields_set:
            # mesh requested explicitly → shard streaming over fsdp×data;
            # without a mesh block the engine stays single-device (the
            # pre-round-4 behavior)
            topo_obj = _topo.MeshTopology.build(cfg_obj.mesh)
            bad_axes = {a: topo_obj.axis_size(a)
                        for a in ("tensor", "pipe", "sequence", "expert")
                        if topo_obj.axis_size(a) > 1}
            if bad_axes:
                raise ValueError(
                    f"offload_param streaming composes with data/fsdp mesh "
                    f"axes only; got {bad_axes}")
            mesh = topo_obj.mesh
        engine = ZeroInfinityEngine(model, cfg_obj, rng=rng, mesh=mesh)
        return engine, None, None, None

    engine_cls = DeepSpeedTpuEngine
    if cfg_obj.hybrid_engine.enabled:
        # RLHF train↔generate engine (reference __init__.py:158 selects
        # DeepSpeedHybridEngine the same way)
        from .runtime.hybrid_engine import DeepSpeedTpuHybridEngine

        engine_cls = DeepSpeedTpuHybridEngine

    engine = engine_cls(args=args,
                        model=model,
                        optimizer=optimizer,
                        model_parameters=model_parameters,
                        training_data=training_data,
                        lr_scheduler=lr_scheduler,
                        mesh=mesh,
                        collate_fn=collate_fn,
                        config=config,
                        rng=rng)
    return engine, engine.optimizer, engine.training_dataloader, engine.lr_scheduler


def init_inference(model=None, config=None, **kwargs):
    """Create the inference engine (reference deepspeed/__init__.py:269)."""
    from .inference.engine import InferenceEngine

    return InferenceEngine(model, config=config, **kwargs)


def init_distributed(dist_backend="xla", **kwargs):
    from .comm import init_distributed as _init

    return _init(dist_backend=dist_backend, **kwargs)


# the process's recorder of program builds and full collections listens
# from here on (telemetry/builds.py): before anything the package builds.
# At the end of the file, so that no line above it moves.
from .telemetry.builds import RECORDER as _BUILDS  # noqa: E402

_BUILDS.start()
