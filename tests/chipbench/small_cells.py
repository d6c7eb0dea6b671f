"""The benchmark's cells at a size the CPU tests can hold."""
import dataclasses

from chipbench import registry


def small(name: str, **sizes) -> registry.Cell:
    """The cell at the program's reduced chatglm3-6b sizes (``sizes``
    overrides them): two prefixes of one block, short turns, batches of
    two; outputs longer than the prompts, so that most of what a decode
    step attends to is what decoding wrote."""
    cell = registry.cell(name)
    dims = dict(num_layers=2, hidden_size=256, num_attention_heads=4,
                multi_query_group_num=2, kv_channels=64,
                ffn_hidden_size=512, padded_vocab_size=512)
    dims.update(sizes)
    c = dict(cell.config, **dims)
    c["kv"] = dict(c["kv"], blocks=256)
    c["scheduler"] = dict(c["scheduler"], max_batch_requests=2)
    c["check"] = dict(c["check"], requests=3)
    cell.config = c
    t = dict(cell.traffic, max_new_tokens=24)
    t["prefixes"] = dict(t["prefixes"], count=2, tokens=16)
    if t["loop"] == "open":
        t.update(suffixes=[[16, 1]], rate_per_s=3.0)
    else:
        t.update(suffixes=[[8, 1]], outstanding=4)
    cell.traffic = t
    return cell


def small_arch(conf: dict):
    """The program's chatglm3-6b `ArchConfig` at the sizes of ``conf``."""
    from repro.configs import get_config
    return dataclasses.replace(
        get_config(conf["program_arch"]), n_layers=conf["num_layers"],
        d_model=conf["hidden_size"], n_heads=conf["num_attention_heads"],
        n_kv_heads=conf["multi_query_group_num"],
        head_dim=conf["kv_channels"], d_ff=conf["ffn_hidden_size"],
        vocab_size=conf["padded_vocab_size"])
