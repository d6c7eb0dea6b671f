"""Decides ``correct``: the served tokens against the plain float32
reference.

After the window, a sample of the completed requests, drawn from the seed
and always holding the longest prompt, is run through the reference once
over each prompt with each of its served samples. The traffic samples at
its own temperature and the program reports, for every token it served,
the log-probability its logits gave that token (``log_softmax`` at
temperature 1). The numbers compared, each that the configuration's
``check`` / ``limits`` names, are taken from the gaps, over every served
token of the sample, between that log-probability and the reference's for
the same token after the same tokens: the widest (`max_lp_err`) or the
mean (`mean_lp_err`). Beside them, every
compared request must have returned ``n_samples`` samples of ``max_new``
tokens inside the vocabulary (`bad_samples`, limit 0).

The control is the reference with its weights in the precision below the
configuration's, put in the program's place: its log-probabilities of the
same tokens go through the same comparison.
"""
from __future__ import annotations

import sys
from dataclasses import dataclass
from typing import Any, Dict, List, Sequence, Tuple

import numpy as np

from chipbench import registry
from chipbench.traffic import seed_words


@dataclass
class Served:
    """One completed request as the program returned it."""
    req: Any                       # chipbench.traffic.Request
    samples: List[np.ndarray]      # token ids, one array per sample
    lps: np.ndarray                # (samples, tokens) log-probabilities
    batch_seqs: int = 0            # sequences of the batch that served it


def sample(served: Sequence[Served], k: int, seed: int) -> List[int]:
    """Indices of up to ``k`` requests: the longest prompt, and the rest
    drawn from the seed."""
    if not served:
        return []
    longest = max(range(len(served)),
                  key=lambda i: len(served[i].req.prompt))
    rest = [i for i in range(len(served)) if i != longest]
    rng = np.random.default_rng(seed_words(seed) + [5])
    pick = rng.permutation(rest)[: max(0, k - 1)].tolist()
    return [longest] + sorted(pick)


def items_of(served: Sequence[Served], picked: Sequence[int]
             ) -> List[Tuple[np.ndarray, int, np.ndarray]]:
    """One item per sample of the picked requests: the prompt followed by
    the sample's tokens, the index of its first served token, and the
    program's log-probabilities of them."""
    items = []
    for i in picked:
        s = served[i]
        for toks, lps in zip(s.samples, s.lps):
            seq = np.concatenate([s.req.prompt, np.asarray(toks, np.int32)])
            items.append((seq, len(s.req.prompt),
                          np.asarray(lps, np.float32)))
    return items


def bad_samples(conf: dict, served: Sequence[Served]) -> int:
    vocab = conf["padded_vocab_size"]
    bad = 0
    for s in served:
        if len(s.samples) != s.req.n_samples or \
                np.shape(s.lps) != (s.req.n_samples, s.req.max_new):
            bad += 1
            continue
        for toks in s.samples:
            toks = np.asarray(toks)
            if toks.shape != (s.req.max_new,) or (toks < 0).any() or \
                    (toks >= vocab).any():
                bad += 1
    return bad


def stats(ref_lps: Sequence[np.ndarray], lps: Sequence[np.ndarray]
          ) -> Dict[str, float]:
    """The gaps between ``lps`` and the reference's, over every served
    token of the items whose shapes agree: the widest (`max_lp_err`) and
    the mean (`mean_lp_err`)."""
    gaps = [np.abs(np.asarray(a, np.float64) - np.asarray(b, np.float64))
            for a, b in zip(lps, ref_lps) if np.shape(a) == np.shape(b)]
    if not gaps:
        return {}
    g = np.concatenate([x.ravel() for x in gaps])
    return {"max_lp_err": float(g.max()), "mean_lp_err": float(g.mean())}


def compare(conf: dict, ref_lps: Sequence[np.ndarray],
            lps: Sequence[np.ndarray], bad: int) -> Dict[str, Any]:
    """The comparison that decides ``correct``, of ``lps`` (the program's,
    or a control's) against the reference's, item by item: each number that
    the configuration's ``check`` / ``limits`` names, and `bad_samples`
    (an item of the wrong shape is one)."""
    got = stats(ref_lps, lps)
    compared = {name: {"value": got.get(name), "limit": limit}
                for name, limit in conf["check"]["limits"].items()}
    compared["bad_samples"] = {"value": bad, "limit": 0}
    return {"correct": all(c["value"] is not None and
                           c["value"] <= c["limit"]
                           for c in compared.values()),
            "compared": compared, "stats": got}


def check(conf: dict, seed: int, served: Sequence[Served],
          controls: Sequence[str] = ()) -> Dict[str, Any]:
    """``correct`` and the numbers compared, for the program. For each
    weight format of ``controls``, also ``controls[fmt]``: the verdict and
    numbers of the same comparison with that control in the program's
    place. The benchmark's runs compute none."""
    picked = sample(served, conf["check"]["requests"], seed)
    items = items_of(served, picked)
    bad = bad_samples(conf, [served[i] for i in picked])
    log(f"[check] {len(picked)} of {len(served)} completed requests "
        f"(batches of {sorted(served[i].batch_seqs for i in picked)} "
        f"sequences), {len(items)} samples, "
        f"{sum(len(it[2]) for it in items)} served tokens compared with the "
        f"reference")
    if not items:
        return compare(conf, [], [], bad)
    ref = registry.reference(conf["reference"])
    got = ref.readings(conf, seed_words(seed),
                       [(seq, first) for seq, first, _ in items],
                       conf["weights"], control_fmts=controls)
    ref_lps = [g["lp"] for g in got]
    out = compare(conf, ref_lps, [it[2] for it in items], bad)
    if controls:
        out["controls"] = {f: compare(conf, ref_lps,
                                      [g[f"lp:{f}"] for g in got], bad)
                           for f in controls}
    return out


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)
