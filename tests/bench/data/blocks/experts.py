"""A block for the tests alone: the first ``first_k_dense_replace`` layers
dense, the rest a softmax router over ``num_local_experts`` gated experts of
which each token takes ``num_experts_per_tok``, renormalised over those. It
shows that a block with layers of two kinds and a leaf shaped [L, E, d, f]
is added as files: the program serves no such block yet."""
from typing import Dict, List


def _dims(conf: Dict):
    d, H = conf["hidden_size"], conf["num_attention_heads"]
    L, dense = conf["num_hidden_layers"], conf["first_k_dense_replace"]
    return (d, H, conf["num_key_value_heads"], conf.get("head_dim") or d // H,
            conf["vocab_size"], dense, L - dense)


def program_config(conf: Dict):
    raise ValueError(f"{conf['name']}: the program's paged engine serves no "
                     "expert block")


def layout(conf: Dict) -> Dict:
    d, H, K, hd, V, Ld, Le = _dims(conf)
    E, f = conf["num_local_experts"], conf["intermediate_size"]
    fd = conf["dense_intermediate_size"]

    def attention(L):
        return {"ln1/scale": ((L, d), "norm"), "ln2/scale": ((L, d), "norm"),
                "mixer/wq": ((L, d, H * hd), d),
                "mixer/wk": ((L, d, K * hd), d),
                "mixer/wv": ((L, d, K * hd), d),
                "mixer/wo": ((L, H * hd, d), H * hd)}
    dense = {**attention(Ld), "ffn/w_gate": ((Ld, d, fd), d),
             "ffn/w_up": ((Ld, d, fd), d), "ffn/w_down": ((Ld, fd, d), fd)}
    moe = {**attention(Le), "ffn/router": ((Le, d, E), d),
           "ffn/w_gate": ((Le, E, d, f), d), "ffn/w_up": ((Le, E, d, f), d),
           "ffn/w_down": ((Le, E, f, d), f)}
    top = {"embed": ((V, d), "embed"), "final_norm/scale": ((d,), "norm"),
           "lm_head": ((d, V), d)}
    return {"top": top, "groups": [[dense], [moe]]}


def layer_flops(conf: Dict) -> List[int]:
    d, H, K, hd, _, Ld, Le = _dims(conf)
    attn = 2 * 2 * d * H * hd + 2 * 2 * d * K * hd
    dense = attn + 3 * 2 * d * conf["dense_intermediate_size"]
    E, k, f = (conf["num_local_experts"], conf["num_experts_per_tok"],
               conf["intermediate_size"])
    moe = attn + 2 * d * E + k * 3 * 2 * d * f
    return [dense] * Ld + [moe] * Le
