"""The comparison that decides ``correct`` for a served model.

Once the window has closed, a sample of the requests it finished, drawn
from the seed, is taken from what the timed path produced: each request's
prompt and every token the program fed back.  The plain float32 reference
is run once over each prompt with its served tokens, and the number
compared is the widest gap by which a served token's logit lies below the
reference's best at that position (greedy decoding: a sound program serves
the reference's first choice, or one within rounding of it).
"""
from __future__ import annotations

import random
from typing import Any, Dict, List, Sequence, Tuple

import numpy as np


def missing_rows(batches, gen_len: int) -> int:
    """Members the scheduler handed to the executor for which the program
    computed no row, or fed back fewer than ``gen_len`` tokens."""
    return sum(b.n if b.prompt is None or len(b.fed) != gen_len
               else max(0, b.n - b.prompt.shape[0]) for b in batches)


def sample(batches, gen_len: int, k: int, seed: int
           ) -> Tuple[np.ndarray, np.ndarray]:
    """``k`` finished requests drawn from the seed: (tokens (k, P+G-1),
    served (k, G)) as host arrays.  A request is a member row of a batch
    (rows beyond the members pad the bucket)."""
    rows = [(i, r) for i, b in enumerate(batches)
            if b.prompt is not None and len(b.fed) == gen_len
            for r in range(min(b.n, b.prompt.shape[0]))]
    if not rows:
        raise RuntimeError("the window finished no request to compare")
    picks = sorted(random.Random(seed).sample(rows, min(k, len(rows))))
    toks, served = [], []
    for i, r in picks:
        b = batches[i]
        prompt = np.asarray(b.prompt[r])
        fed = np.asarray([int(np.asarray(t[r, 0])) for t in b.fed])
        toks.append(np.concatenate([prompt, fed[:-1]]))
        served.append(fed)
    return np.stack(toks).astype(np.int32), np.stack(served).astype(np.int32)


def positions(prompt_len: int, gen_len: int, k: int) -> np.ndarray:
    """Positions whose logits choose each served token: the prompt's last
    position chooses the first, and each served token's position the
    next."""
    return np.tile(np.arange(prompt_len - 1, prompt_len - 1 + gen_len,
                             dtype=np.int32), (k, 1))


def gaps(ref: np.ndarray, chosen: np.ndarray) -> np.ndarray:
    """ref (k, G, V) float32 logits; chosen (k, G) token ids.  The gap of
    each chosen token below the reference's best, in logits."""
    best = ref.max(axis=-1)
    got = np.take_along_axis(ref, chosen[..., None], axis=-1)[..., 0]
    return best - got


def reference_logits(arch, cfg: Dict[str, Any], weights, toks: np.ndarray,
                     at: np.ndarray, rows: int, quant: str = ""
                     ) -> np.ndarray:
    """The reference's logits, ``rows`` sequences at a time so that it
    fits beside the weights."""
    import jax.numpy as jnp
    out = []
    for i in range(0, len(toks), rows):
        lg = arch.logits(cfg, weights, jnp.asarray(toks[i:i + rows]),
                         jnp.asarray(at[i:i + rows]), quant=quant)
        out.append(np.asarray(lg, np.float32))
    return np.concatenate(out)


def compare(arch, cfg, weights, toks, served, prompt_len, gen_len,
            rows: int, controls: Sequence[str] = ()) -> Dict[str, float]:
    """The widest gap of the served tokens, and of the token each control
    precision would put first, under the float32 reference."""
    at = positions(prompt_len, gen_len, len(toks))
    ref = reference_logits(arch, cfg, weights, toks, at, rows)
    if not np.isfinite(ref).all():
        raise RuntimeError("the reference's logits are not finite")
    out = {"max_logit_gap": float(gaps(ref, served).max())}
    for q in controls:
        low = reference_logits(arch, cfg, weights, toks, at, rows, quant=q)
        out[f"control_{q}"] = float(gaps(ref, low.argmax(-1)).max())
    return out


def verdict(readings: Dict[str, float], limits: Dict[str, float]
            ) -> Tuple[bool, List[str]]:
    """Each number compared beside its limit; correct when none exceeds
    it (and none is missing)."""
    ok, lines = True, []
    for name, limit in limits.items():
        v = readings.get(name)
        good = v is not None and v <= limit
        ok &= good
        lines.append(f"check {name}={v!r} limit={limit!r} "
                     f"{'ok' if good else 'FAIL'}")
    return ok, lines
