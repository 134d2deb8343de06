"""Operations and bytes of a routed FFN, for any configuration: the one thing
that differs between them is the key under which the configuration's file
states its experts' width (``intermediate_size`` where every FFN is routed,
``moe_intermediate_size`` where a dense layer keeps that name for itself), so
the caller names it. A metric's file passes it through ``params``
(``width_key``): the next routed configuration adds a metric file and no code.
"""

from __future__ import annotations


def routed_ffn_cost(cfg: dict, pairs: float, experts_hit: float, width_key: str,
                    bytes_per_el: int = 2):
    """-> (flops, bytes) of the routed FFN for ``pairs`` token-expert pairs of
    the held experts that reached ``experts_hit`` of them (both summed over
    the layers of a call, as the program counts them), at the experts' width
    ``cfg[width_key]``.

    FLOPs: the gate, up and down projections of each pair, ``d x f`` MACs
    each. Bytes: the three matrices of every held expert that was hit, once,
    plus each pair's input row read and output row written (the ``f``-wide
    intermediate between the projections is the implementation's choice and
    is left out)."""
    d, f = cfg["hidden_size"], cfg[width_key]
    flops = 2.0 * 3 * pairs * d * f
    weight_bytes = experts_hit * 3 * d * f * bytes_per_el
    activation_bytes = pairs * 2 * d * bytes_per_el
    return flops, float(weight_bytes + activation_bytes)
