"""laguna_xs2: the system under test, and the counts its metrics need.

`build` goes through the program's public entry points only
(`models.laguna.build_pretrain_program`, `Executor`); the one exception,
`hbm`, is the ERNIE adapter's (benchmark/program_access.py). What an adapter
of a model with expert counters does after it is built (`start`, `step` with
the counters fetched beside the loss, `record`, `update_norms`) is the
Nemotron adapter's `System`, taken by its public name. The plain reference
is beside this file, in laguna_xs2_reference.py, and imports none of this."""
from __future__ import annotations

from benchmark.configs import nemotron3_nano
# at import, so that a tree without the model fails when the cell is loaded
# and not after the reference has run
from paddle_tpu.models import laguna

BYTES_BF16, BYTES_F32 = 2, 4
FULL, SLIDING = "full_attention", "sliding_attention"


# ---------------------------------------------------------------------------
# operations and bytes, from the shapes: required work only
# ---------------------------------------------------------------------------

def visible_pairs(t: int, window=None) -> int:
    """(query, key) pairs a head of one sequence must score: the causal half
    with its diagonal, or the band of a sliding window (a query's own key
    and the `window - 1` before it)."""
    if window is None or window >= t:
        return t * (t + 1) // 2
    return window * (window + 1) // 2 + (t - window) * window


def counts(cfg: dict, traffic: dict) -> dict:
    """Operations the forward pass requires a token (x3 with the backward
    pass; what remat, the experts' tiles and the kernels recompute does not
    count), part by part, and the operations and bytes of the parts that
    have a roofline share or a bytes bound of their own.

    The attention kernels do QK^T and PV (4 x head_dim operations a visible
    pair a query head, forward) over the pairs the layer's kind lets a query
    see: the causal half in a full layer, THE BAND in a window layer (not
    the tiles a kernel schedules: a tile's masked part is no required work);
    three times that with the backward pass. Their bytes are q, out and
    their cotangents at the layer's query heads x head_dim and k, v and
    theirs at the key/value heads x head_dim, each read or written once in
    bf16. `attn_*_per_step` sums both kinds (what
    `layer_metrics/attn_roofline.py` holds against every Mosaic call),
    `swa_*_per_step` the window layers alone. The routed experts' work is
    that of the pairs expected on the experts held, tokens x top_k x held /
    experts (`experts_flops_per_pair` lets a reader that knows the pairs a
    step really held count those instead). The head multiplies by the
    vocabulary slice."""
    d, hd = cfg["hidden_size"], cfg["head_dim"]
    b, t = traffic["batch"], traffic["seq_len"]
    tokens = b * t
    n = cfg["num_hidden_layers"]
    kinds = cfg["layer_types"][:n]
    heads = cfg["num_attention_heads_per_layer"][:n]
    kv_dim = cfg["num_key_value_heads"] * hd
    n_dense = cfg["mlp_layer_types"][:n].count("dense")
    n_moe = n - n_dense

    proj_fwd = kernel_fwd = 0.0          # a token, summed over the layers
    flops = {FULL: 0, SLIDING: 0}        # the kernels', a step, by kind
    nbytes = {FULL: 0, SLIDING: 0}
    for kind, nh in zip(kinds, heads):
        q_dim = nh * hd
        proj_fwd += 2 * d * (q_dim + 2 * kv_dim) + 2 * d * nh + 2 * q_dim * d
        pairs = visible_pairs(
            t, cfg["sliding_window"] if kind == SLIDING else None)
        kernel = 4 * hd * nh * pairs * b          # forward, a step
        kernel_fwd += kernel / tokens
        flops[kind] += 3 * kernel
        nbytes[kind] += 4 * (q_dim + kv_dim) * tokens * BYTES_BF16
    dense_fwd = 2 * 3 * d * cfg["intermediate_size"]
    experts = cfg["num_experts_published"]
    held, k = cfg["experts_held"][1], cfg["num_experts_per_tok"]
    pair_fwd = 2 * 3 * d * cfg["moe_intermediate_size"]
    shared_fwd = 2 * 3 * d * cfg["shared_expert_intermediate_size"]
    pairs = tokens * k * held / experts
    moe_fwd = 2 * d * experts + shared_fwd + pair_fwd * k * held / experts
    head_fwd = 2 * d * cfg["vocab_size"]
    fwd = (proj_fwd + kernel_fwd + n_dense * dense_fwd + n_moe * moe_fwd
           + head_fwd)

    # the grouped products' bytes, a layer: the held experts' three matrices
    # read in bf16 forward and backward, their gradients written in float32,
    # and a pair's row in and out, forward and backward
    expert_params = held * 3 * d * cfg["moe_intermediate_size"]
    experts_bytes = (expert_params * (2 * BYTES_BF16 + BYTES_F32)
                     + pairs * d * 4 * BYTES_BF16)
    return {
        "tokens_per_step": tokens,
        "flops_per_token": 3 * fwd,
        "fwd_flops_per_token": {
            "attention_projections": proj_fwd,
            "attention_kernel": kernel_fwd, "dense_mlp": n_dense * dense_fwd,
            "moe": n_moe * moe_fwd, "lm_head": head_fwd},
        "attn_flops_per_step": flops[FULL] + flops[SLIDING],
        "attn_bytes_per_step": nbytes[FULL] + nbytes[SLIDING],
        "swa_flops_per_step": flops[SLIDING],
        "swa_bytes_per_step": nbytes[SLIDING],
        "experts_flops_per_pair": 3 * pair_fwd,
        "experts_pairs_per_step": pairs * n_moe,
        "experts_flops_per_step": 3 * pair_fwd * pairs * n_moe,
        "experts_bytes_per_step": experts_bytes * n_moe,
        "moe_blocks": n_moe,
        "pairs_routed_per_step": tokens * k * n_moe,
    }


def work_per_step(cfg: dict, traffic: dict) -> int:
    return traffic["batch"] * traffic["seq_len"]


# ---------------------------------------------------------------------------
# the system under test
# ---------------------------------------------------------------------------

def model_config(cfg: dict) -> "laguna.LagunaConfig":
    if cfg["attention_bias"] or cfg["tie_word_embeddings"]:
        raise ValueError("laguna_xs2: models/laguna.py builds no attention "
                         "bias and an untied head")
    same = ("vocab_size", "hidden_size", "intermediate_size",
            "num_hidden_layers", "num_attention_heads",
            "num_key_value_heads", "head_dim", "rms_norm_eps", "gating",
            "sliding_window", "num_experts_per_tok", "moe_intermediate_size",
            "shared_expert_intermediate_size", "moe_routed_scaling_factor",
            "moe_apply_router_weight_on_input", "norm_topk_prob",
            "initializer_range")
    n = cfg["num_hidden_layers"]
    return laguna.LagunaConfig(
        layer_types=list(cfg["layer_types"][:n]),
        mlp_layer_types=list(cfg["mlp_layer_types"][:n]),
        num_attention_heads_per_layer=list(
            cfg["num_attention_heads_per_layer"][:n]),
        rope_parameters={kind: dict(cfg["rope_parameters"][kind])
                         for kind in (FULL, SLIDING)},
        num_experts=cfg["num_experts_published"],
        experts_held=tuple(cfg["experts_held"]),
        **{key: cfg[key] for key in same})


class System(nemotron3_nano.System):
    """One compiled training step with its state: built once in set-up,
    checked on its first steps and handed as it is to the window."""

    def __init__(self, cfg: dict, traffic: dict, chips: int):
        import paddle_tpu as fluid
        from paddle_tpu.contrib import mixed_precision as mp

        if chips != 1 or traffic.get("layout", "single") != "single":
            raise ValueError("laguna_xs2 runs on one chip, layout single")
        opt_cfg = cfg["optimizer"]
        self._beta1 = opt_cfg["beta1"]

        def opt():
            # called while the program is built: the schedule's op and its
            # step counter are the program's
            lr, warm = opt_cfg["learning_rate"], opt_cfg.get("warmup_steps")
            if warm:        # step t = 1, 2, ... runs at lr * min(1, t / warm)
                lr = fluid.layers.linear_lr_warmup(
                    lr, warm, start_lr=lr / warm, end_lr=lr + lr / warm)
            adam = fluid.optimizer.Adam(
                lr, beta1=opt_cfg["beta1"], beta2=opt_cfg["beta2"],
                epsilon=opt_cfg["epsilon"])
            if cfg["amp_dtype"] is None:      # float32, the CPU tests' preset
                return adam
            return mp.decorate(adam, dtype=cfg["amp_dtype"],
                               use_dynamic_loss_scaling=False)

        self._fluid, self._model = fluid, laguna
        self._tokens = traffic["batch"] * traffic["seq_len"]
        self._k = cfg["num_experts_per_tok"]
        with fluid.unique_name.guard():     # the same names every build
            self.main, self.startup, _, self.loss, self.counters = (
                laguna.build_pretrain_program(
                    model_config(cfg), traffic["batch"], traffic["seq_len"],
                    optimizer_factory=opt))
        self._fetch = [self.loss] + [v for _, tokens, pairs in self.counters
                                     for v in (tokens, pairs)]
        self.exe = fluid.Executor(fluid.TPUPlace())
        self.scope = fluid.Scope()
        self.program = self.main


def build(cfg: dict, traffic: dict, chips: int) -> System:
    return System(cfg, traffic, chips)
