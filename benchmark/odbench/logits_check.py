"""What a serving cell's check does whatever its configuration: the check's
prompts through the engine under test, and the relative L2 between two sets
of logits rows. A driver brings its own reference and its own limit. (The
OLMoE driver holds an older copy of both functions, and the granite driver
imports that one: a ``benchmark`` PR points both here, PERF.md section 7(f).)
"""

from __future__ import annotations

import math

import numpy as np

from odbench import traffic


def served_rows(cell, engine, seed, after_admit=None):
    """The check's prompts (``options["check"]``: ``prompt_tokens``, drawn
    from ``seed``) through the engine: prefill, then ``decode_steps`` steps
    through the cache -> (prompts, the token sequence each was fed, the logits
    rows of its last prompt position and of each decode step).
    ``after_admit(engine, prompts)``, if given, runs between the prefills and
    the first step (the readings tool plants a fault in the cache there)."""
    spec = cell.options["check"]
    rng = traffic.rng_for(seed, 3)
    vocab = cell.config["vocab_size"]
    prompts = [rng.integers(traffic.FIRST_TOKEN, vocab, n).tolist() for n in spec["prompt_tokens"]]
    steps = int(spec["decode_steps"])
    tokens = np.zeros(engine.num_slots, np.int32)
    cache_lens = np.zeros(engine.num_slots, np.int32)
    seqs, got = [], []
    for slot, prompt in enumerate(prompts):
        tok, logits = engine.admit(slot, prompt)
        tokens[slot], cache_lens[slot] = tok, len(prompt)
        seqs.append(list(prompt) + [tok])
        got.append([np.asarray(logits, np.float32)])
    if after_admit is not None:
        after_admit(engine, prompts)
    for step in range(steps):
        nxt, logits = engine.decode_step(tokens, cache_lens)
        logits = np.asarray(logits, np.float32)
        for slot in range(len(prompts)):
            got[slot].append(logits[slot])
            tokens[slot] = nxt[slot]
            cache_lens[slot] += 1
            if step < steps - 1:
                seqs[slot].append(int(nxt[slot]))
    return prompts, seqs, [np.stack(rows) for rows in got]


def rel_l2(have: list, want: list):
    """-> (relative L2 over all rows, the same per prompt)."""
    num = [float(np.sum((h - w) ** 2)) for h, w in zip(have, want)]
    den = [float(np.sum(w**2)) for w in want]
    return math.sqrt(sum(num) / sum(den)), [math.sqrt(n / d) for n, d in zip(num, den)]
