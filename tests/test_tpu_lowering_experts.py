"""The experts' grouped product at the widths of each expert cell, loss and
gradients, through the TPU's own compiler (a file of its own beside
`test_tpu_lowering.py`: under `--dist loadfile` a file is one worker's, and
these four compiles are a hundred seconds)."""
import jax
import jax.numpy as jnp
import pytest

from tpu_lowering_base import _dispatch_as_on_tpu, one_chip  # noqa: F401


@pytest.mark.parametrize("d,h,e,held,k,gated,act", [
    (2688, 1856, 128, 8, 6, False, "relu2"),
    (2048, 1536, 64, 8, 4, True, "silu"),
    (2048, 768, 256, 16, 8, True, "silu"),
    (2048, 512, 256, 32, 8, True, "silu"),
    (2304, 1024, 256, 8, 8, True, "silu"),
], ids=["nemotron", "lfm2", "joyai", "laguna", "kimi_linear"])
def test_grouped_expert_product_compiles_for_v5e_at_the_cells_widths(
        one_chip, d, h, e, held, k, gated, act):
    """The routed experts of one block of each expert cell (16,384 tokens of
    b2 x T8192, bf16 activations over float32 masters; Nemotron's plain
    relu^2 experts of 2688 x 1856, 14.5 lane tiles wide, and the four gated
    shapes; Kimi Linear's d 2,304 is 18 lane tiles and no multiple of 512:
    `grouped_path` says "pallas" there too), loss and gradients, through the TPU's own compiler: the two
    kernels and no loop over tiles, nothing of the size of all the pairs'
    rows, and the kernels' own buffers (a float32 row a token forward, two
    backward) within 0.9 GiB."""
    from paddle_tpu.ops.common import act_map
    from paddle_tpu.parallel import moe

    n = 16384
    assert moe.grouped_path(d, h, gated, jnp.bfloat16, moe.TILE) == "pallas"

    def loss(x, gate, w1, w2, w3=None):
        out = moe.moe_ffn(x, gate, w1, None, w2, None, k=k,
                          act=act_map()[act], experts_held=(0, held),
                          scoring="sigmoid", routed_scaling=2.5, w3=w3)
        return jnp.sum(out.y.astype(jnp.float32)), out.pairs_held

    shapes = [((n, d), jnp.bfloat16), ((d, e), jnp.float32),
              ((held, d, h), jnp.float32), ((held, h, d), jnp.float32)]
    if gated:
        shapes.append(((held, d, h), jnp.float32))
    args = [jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
            for shape, dtype in shapes]
    compiled = jax.jit(jax.value_and_grad(
        loss, tuple(range(len(args))), has_aux=True)).trace(*args).lower(
            lowering_platforms=("tpu",)).compile()
    text = compiled.as_text()
    # the rows laid out and the walk, forward and backward
    assert text.count("tpu_custom_call") == 4
    # the dispatch's search for each tile's expert is the one loop left
    assert text.count(" while(") == 1 and "searchsorted" in text
    assert f"[{n * k},{d}]" not in text and f"[{n * k},{h}]" not in text
    assert compiled.memory_analysis().temp_size_in_bytes < 0.9 * 2 ** 30
