"""``paged_attn_roofline`` for a configuration in which only some layers keep
a cache: the same reader over the same events and traced rows, told the
number of attention layers in the pattern where it asks for the layers (so
``costs.paged_decode_cost`` counts the one ring of this cell, not ten)."""

import dataclasses
import os

from odbench import costs_granite_h, manifest

_all_layers = manifest.load_module(
    os.path.join(os.path.dirname(os.path.abspath(__file__)), "paged_attn_roofline.py")
)


def read(obs, params):
    cell = obs["cell"]
    if "layer_types" not in cell.config:
        return None  # every layer has a ring: ``paged_attn_roofline`` reads that cell
    cached = costs_granite_h.layer_kinds(cell.config).count("attention")
    cell = dataclasses.replace(cell, config={**cell.config, "num_hidden_layers": cached})
    return _all_layers.read({**obs, "cell": cell}, params)
