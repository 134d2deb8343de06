"""``paged_attn_roofline`` for a configuration whose heads have a size of their
own (``head_dim`` a key of its file, not ``hidden_size / num_attention_heads``):
the same reader over the same events and traced rows, told the heads' true
width where it derives it (so ``costs.paged_decode_cost`` counts rows of
``num_key_value_heads x head_dim`` values, not of the derived size)."""

import dataclasses
import os

from odbench import manifest

_derived = manifest.load_module(
    os.path.join(os.path.dirname(os.path.abspath(__file__)), "paged_attn_roofline.py")
)


def read(obs, params):
    cell = obs["cell"]
    if "head_dim" not in cell.config:
        return None  # the derived size is right: ``paged_attn_roofline`` reads that cell
    width = cell.config["num_attention_heads"] * cell.config["head_dim"]
    cell = dataclasses.replace(cell, config={**cell.config, "hidden_size": width})
    return _derived.read({**obs, "cell": cell}, params)
