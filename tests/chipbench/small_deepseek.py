"""The DeepSeek-V2-Lite cell at a size the CPU tests can hold: its own
small sizing, since `small_cells.small` sizes ChatGLM's keys."""
import dataclasses

from chipbench import registry

# every width cut, the shapes kept: a dense layer then MoE layers, 16
# published routed experts of which this share holds 4, top-6, 2 shared,
# MLA with a latent, a rope key and YaRN
DIMS = dict(num_hidden_layers=3, hidden_size=64, num_attention_heads=4,
            intermediate_size=128, moe_intermediate_size=32,
            n_routed_experts=4, kv_lora_rank=32, qk_nope_head_dim=16,
            qk_rope_head_dim=8, v_head_dim=16, vocab_size=512)


def small_config(conf: dict, **sizes) -> dict:
    dims = dict(DIMS, **sizes)
    c = dict(conf, **dims)
    c["expert_parallel"] = dict(c["expert_parallel"],
                                published_n_routed_experts=16)
    # the harness's names for the same sizes
    c.update(num_layers=c["num_hidden_layers"],
             multi_query_group_num=c["num_attention_heads"],
             kv_channels=c["hidden_size"] // c["num_attention_heads"],
             ffn_hidden_size=c["intermediate_size"],
             padded_vocab_size=c["vocab_size"])
    c["kv"] = dict(c["kv"], blocks=256)
    c["scheduler"] = dict(c["scheduler"], max_batch_requests=2)
    c["check"] = dict(c["check"], requests=3)
    return c


def small(name: str, **sizes) -> registry.Cell:
    """The cell at the small sizes (``sizes`` overrides them): two prefixes
    of four blocks, a short question, batches of two, outputs longer than
    the questions."""
    cell = registry.cell(name)
    cell.config = small_config(cell.config, **sizes)
    t = dict(cell.traffic, max_new_tokens=24, outstanding=4)
    t["prefixes"] = dict(t["prefixes"], count=2, tokens=64)
    t["suffixes"] = [[8, 1]]
    cell.traffic = t
    return cell


def small_arch(conf: dict):
    """The program's `ArchConfig` that ``conf`` names, at its sizes."""
    from repro.configs import get_config
    arch = get_config(conf["program_arch"])
    ep = conf["expert_parallel"]
    return dataclasses.replace(
        arch, n_layers=conf["num_hidden_layers"],
        d_model=conf["hidden_size"], n_heads=conf["num_attention_heads"],
        n_kv_heads=conf["num_attention_heads"],
        d_ff=conf["intermediate_size"], vocab_size=conf["vocab_size"],
        mla=dataclasses.replace(
            arch.mla, kv_lora_rank=conf["kv_lora_rank"],
            qk_nope_head_dim=conf["qk_nope_head_dim"],
            qk_rope_head_dim=conf["qk_rope_head_dim"],
            v_head_dim=conf["v_head_dim"]),
        moe=dataclasses.replace(
            arch.moe, n_experts=ep["published_n_routed_experts"],
            n_held=conf["n_routed_experts"],
            held_offset=ep["held_expert_offset"],
            d_expert=conf["moe_intermediate_size"],
            top_k=conf["num_experts_per_tok"],
            n_shared=conf["n_shared_experts"]))
