"""What `correct` compares for a served token, the same for every
architecture: the architecture file's `logits_at` gives the reference
logits, this reads the served token's margin from them."""

from __future__ import annotations

from typing import Any, Dict, List, Sequence

import jax
import jax.numpy as jnp


class Frozen(dict):
    """A configuration usable as a static jit argument."""

    def __hash__(self):
        return hash(tuple(sorted((k, str(v)) for k, v in self.items())))


def served_token_margins(arch, params, prompt: Sequence[int], served: Sequence[int], config: Dict[str, Any],
                         which: Sequence[int]) -> List[float]:
    """For served tokens number `which` (0 = the first token, from prefill;
    i > 0 = the i-th decode step through the paged cache): reference maximum
    logit at that position minus the reference logit of the token that was
    served, teacher-forced on the served tokens before it. 0 when the
    served token is the reference argmax; small when rounding flipped two
    near-equal logits; large when the served path computed something else."""
    seq = jnp.asarray(list(prompt) + list(served[: max(which)]), jnp.int32)
    pos = jnp.asarray([len(prompt) - 1 + i for i in which], jnp.int32)
    logits = jax.jit(arch.logits_at, static_argnames=("config",))(params, seq, pos, config=Frozen(config))
    tok = jnp.asarray([served[i] for i in which], jnp.int32)
    margins = jnp.max(logits, axis=-1) - jnp.take_along_axis(logits, tok[:, None], axis=-1)[:, 0]
    return [float(x) for x in margins]
