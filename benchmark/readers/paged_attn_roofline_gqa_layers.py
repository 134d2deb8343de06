"""``paged_attn_roofline`` for a configuration that names its attention layers
in ``gqa_layers`` (every other layer keeps a state and no ring) and whose heads
have a size of their own: the same reader over the same events and traced
rows, told the attention layers among those run where it asks for the layers
and the heads' true width where it derives it (so ``costs.paged_decode_cost``
counts the one ring of this cell, not four). Nothing in a cell without the
key."""

import dataclasses
import os

from odbench import manifest

_all_layers = manifest.load_module(
    os.path.join(os.path.dirname(os.path.abspath(__file__)), "paged_attn_roofline.py")
)


def read(obs, params):
    cell = obs["cell"]
    cfg = cell.config
    if "gqa_layers" not in cfg or "head_dim" not in cfg:
        return None
    cached = sum(1 for i in cfg["gqa_layers"] if i < cfg["num_hidden_layers"])
    width = cfg["num_attention_heads"] * cfg["head_dim"]
    cell = dataclasses.replace(
        cell, config={**cfg, "num_hidden_layers": cached, "hidden_size": width}
    )
    return _all_layers.read({**obs, "cell": cell}, params)
