"""Driver of serving cells of a latent-attention language model whose
residual stream is several wide (manifold-constrained hyper-connections)
and whose rotary pairs YaRN stretches: what ``serve-mla-lm.py`` does (the
zoo's model behind ``InferStep`` and the default batcher, a closed loop of
callers timed from the client's side, the served tokens, the module's
drafts and the first layer's cached latents held against the plain
reference, the float8-weights and the float8-cache controls), for a model
whose constructor takes the ``xing4_0`` keys. Everything but the
constructor's arguments is that driver's, taken as it takes
``serve-lm.py``'s: its parts read this module's ``_model_kwargs`` in place
of their own.

No expert and no row of the vocabulary is cut in this configuration
(``ep_size`` 1): the router's width is ``n_routed_experts`` and every
expert is held.
"""

GAPS = ("widest_logit_gap", "mean_logit_gap", "mtp_logit_gap", "latent_gap")

NO_END_TOKEN = -1


def _model_kwargs(cfg, latent_dtype=None):
    return dict(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        num_layers=cfg["num_hidden_layers"],
        num_heads=cfg["num_attention_heads"],
        q_lora_rank=cfg["q_lora_rank"], kv_lora_rank=cfg["kv_lora_rank"],
        qk_nope_head_dim=cfg["qk_nope_head_dim"],
        qk_rope_head_dim=cfg["qk_rope_head_dim"],
        v_head_dim=cfg["v_head_dim"],
        intermediate_size=cfg["intermediate_size"],
        first_dense=cfg["first_k_dense_replace"],
        num_experts=cfg["n_routed_experts"],
        experts_per_tok=cfg["num_experts_per_tok"],
        expert_width=cfg["moe_intermediate_size"],
        shared_experts=cfg["n_shared_experts"],
        routed_scaling=cfg["routed_scaling_factor"],
        rope_theta=cfg["rope_theta"], rope_scaling=cfg["rope_scaling"],
        rms_eps=cfg["rms_norm_eps"], hc_mult=cfg["hc_mult"],
        hc_sinkhorn_iters=cfg["hc_sinkhorn_iters"], hc_eps=cfg["hc_eps"],
        hc_clamp=(cfg["mhc_h_res_clamp_min"], cfg["mhc_h_res_clamp_max"]),
        latent_dtype=latent_dtype, dtype=cfg["precision"]["weights"])


def _mla(ctx):
    """``serve-mla-lm.py`` with this model's constructor arguments."""
    mla = ctx.bench.driver("serve-mla-lm")
    mla._model_kwargs = _model_kwargs
    return mla


def run(ctx, with_control=False, latent_dtype=None):
    return _mla(ctx).run(ctx, with_control, latent_dtype)


def control(ctx):
    return _mla(ctx).control(ctx)
