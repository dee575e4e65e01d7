"""The cell ``nemotron-3-super-120b-a12b.reason``'s one-token forwards
compiled for a *described* TPU v5e at the sizes its configuration's file
states -- the period of an attention layer, five Mamba-2 layers and five
LatentMoE layers at the published widths, 33 state slots of ``[128, 64,
128]`` float32 a layer -- at the two buckets the cell's decode steps
take: what the chip's compiler refuses of ``mamba2_step``
(ops/mamba2_ssd.py: the state blocks through the slots in scalar memory,
the pool aliased in and out) shows here and not on the chip, and so does
a copy of the bucket's state among the temporaries. Nothing runs. See
tests/test_tpu_compile.py for the method and tests/tpu_compile_harness.py
for what is shared."""

import re

import jax.numpy as jnp
import pytest
from tpu_compile_harness import (_no_persistent_cache, bucket_id,  # noqa: F401
                                 fits_beside, kernels, lowered, nbytes,
                                 staged_projections, v5e)

from deepspeed_tpu.models.mixers import mamba2
from deepspeed_tpu.ops import mamba2_ssd as ssd

NAME = "nemotron-3-super-120b-a12b"


@pytest.mark.parametrize("bucket", [(32, 1), (16, 1)], ids=bucket_id)
def test_a_decode_step_at_the_files_sizes(v5e, bucket, monkeypatch):
    low, params, cache, cfg = lowered(NAME, v5e[0], bucket, monkeypatch)
    layers = cfg.layers_of("mamba2")
    assert layers == 5
    assert cache["mamba_ssm"].shape == (5, 33, 128, 64, 128)
    assert cache["mamba_ssm"].dtype == jnp.float32
    # in and out, two buffers each, of STEP_GROUPS groups' heads: inside
    # the 16 MiB of VMEM a kernel has without asking for more
    tile = mamba2.state_bytes(cfg) // cfg.mamba_n_groups * ssd.STEP_GROUPS
    assert 4 * tile <= 12 * 2 ** 20
    compiled = low.compile()
    text = compiled.as_text()
    # the state stepped where it lies, once a Mamba-2 layer, under the
    # scope its time is read by
    assert kernels(text).count("mamba2_step") == layers
    scoped = re.findall(r'%mamba2_step[.\d]* = [^\n]*op_name="([^"]*)"', text)
    assert len(scoped) == layers
    assert all("/mamba/mamba_scan/" in s for s in scoped), scoped
    fits_beside(compiled, params, cache, bucket, headroom=2 ** 30)
    # the attention layer's q, k and v held to rows: none of their weights
    # copied, transposed, in front of its dot (``mixers.base.held``; left
    # free: ``bf16[4096,4096]`` and two ``bf16[256,4096]``)
    assert low.as_text().count("@LayoutConstraint") == 3
    assert staged_projections(text, params) == []
    # no copy of the bucket's rows' state ([N, 128, 64, 128] float32, one
    # layer's) among the temporaries: the parent's gather held one, and
    # the fresh rows' zeros and the scatter's operand beside it
    rows = bucket[0] * mamba2.state_bytes(cfg)
    assert compiled.memory_analysis().temp_size_in_bytes < rows // 2, (
        compiled.memory_analysis().temp_size_in_bytes / 2 ** 20,
        rows / 2 ** 20)
