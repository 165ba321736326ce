"""A toy reference module of a decoder with latent attention (MLA) and
sparse experts, such as a configuration of another architecture brings
under ``bench/reference/``: the tables that map the program's
configuration onto the file's keys, and the FLOP count. It holds no model;
the tests register it as ``bench.reference.toy_mla_moe``.

The count follows ``bench.flops``'s rule for DeepSeek-V2's layer held as
one chip's share of an expert-parallel layer: the file's
``n_routed_experts`` are the experts held here, ``n_routed_experts_total``
the published count. A token passes through every shared expert and, on
average, ``num_experts_per_tok x held / total`` of the routed experts held
here; the router scores all of them. The first ``first_k_dense_replace``
layers have a dense MLP of ``intermediate_size``. Attention scores over
``qk_nope_head_dim + qk_rope_head_dim`` and reads values over
``v_head_dim``; a null ``q_lora_rank`` is an uncompressed query.
"""

WIDTHS = {"d_model": "hidden_size", "n_heads": "num_attention_heads",
          "d_ff": "intermediate_size",
          "mla.kv_lora": "kv_lora_rank", "mla.q_lora": "q_lora_rank",
          "mla.qk_nope_dim": "qk_nope_head_dim",
          "mla.qk_rope_dim": "qk_rope_head_dim",
          "mla.v_head_dim": "v_head_dim",
          "moe.d_ff_expert": "moe_intermediate_size",
          "moe.top_k": "num_experts_per_tok",
          "moe.n_shared": "n_shared_experts"}
APPLIED = {"n_layers": "num_hidden_layers", "vocab": "vocab_size",
           "param_dtype": "param_dtype", "compute_dtype": "compute_dtype",
           "norm_eps": "rms_norm_eps", "rope_theta": "rope_theta",
           "remat": "remat", "moe.n_experts": "n_routed_experts"}


def matmul_params(cfg: dict) -> float:
    """Matmul parameters a token passes through, the LM head included."""
    d, h, n = (cfg["hidden_size"], cfg["num_attention_heads"],
               cfg["num_hidden_layers"])
    nope, rope, vd = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                      cfg["v_head_dim"])
    kv, ql = cfg["kv_lora_rank"], cfg["q_lora_rank"]
    q = d * h * (nope + rope) if not ql else d * ql + ql * h * (nope + rope)
    attention = q + d * (kv + rope) + kv * h * (nope + vd) + h * vd * d
    dense = cfg["first_k_dense_replace"]
    total = cfg["n_routed_experts_total"]
    routed = cfg["num_experts_per_tok"] * cfg["n_routed_experts"] / total
    experts = 3 * d * cfg["moe_intermediate_size"] * (
        cfg["n_shared_experts"] + routed)
    return (n * attention + dense * 3 * d * cfg["intermediate_size"]
            + (n - dense) * (experts + d * total)
            + d * cfg["vocab_size"])


def flops_per_token(cfg: dict, seq_len: int) -> float:
    h, n = cfg["num_attention_heads"], cfg["num_hidden_layers"]
    qk = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
    return 6.0 * matmul_params(cfg) + 3 * n * seq_len * h * (
        qk + cfg["v_head_dim"])
