"""What `correct` compares, the same for every architecture: what the TIMED
executables emitted, against the architecture file's plain reference.

- a served model (`served_margins`, `window_sample`): once the window has
  closed, a sample drawn from the seed of the requests it finished, the
  longest among them; the reference runs once over each prompt with its
  served tokens, and every served token's reference logit is held against
  the reference's best at that position. 0 where the served token is the
  reference argmax, small where rounding flipped a near-tie, large where the
  served path (stream, engine, prefix cache, pages, prefill, decode batch)
  computed something else. Judged by the quantiles the traffic file names
  (`judge`): a dense file pins the widest gap, a routed model's file may
  name a lower quantile, since its router resolves a few near-ties the other
  way than the float32 reference by right;
- a trained model (`training_reference`, `norm_gaps`): the compiled step's
  first losses, the first gradient's norm as the optimizer got it (from its
  first moment after one step) and the parameters' change after those steps,
  leaf by leaf, against a reference that trains: the architecture file's
  `sequence_nll` differentiated, plain adamw, the mean over the whole batch
  across the chips;
- the control: the reference with its weight matrices at float8_e4m3's 3
  mantissa bits (`in_fp8`), the mildest form of the precision below the
  configurations' bfloat16, put in the program's place. Never part of a run:
  `tools/control.py` reads it, and the limits of the traffic files lie
  between the program's readings and its;
- `draw_norm_scales`: `init_params` starts every norm's scale at 1, and an
  RMSNorm with unit scale over a fan-in-scaled projection is nearly the
  identity, so a model that leaves a norm out would pass;
- `logit_relative_errors`: per position, the distance between two sets of
  logits relative to the reference's norm: the builder's instrument
  (`tools/olmoe_checks.py`), not a check of any cell.
"""

from __future__ import annotations

import random
from typing import Any, Callable, Dict, List, Mapping, Sequence

import jax
import jax.numpy as jnp
import numpy as np

QUANTILES = {"q50": 0.5, "q90": 0.9, "q99": 0.99, "q100": 1.0}


class Frozen(dict):
    """A configuration usable as a static jit argument."""

    def __hash__(self):
        return hash(tuple(sorted((k, str(v)) for k, v in self.items())))


def reference_logits(arch, params, tokens: Sequence[int], positions: Sequence[int], config: Dict[str, Any]):
    """The architecture file's next-token logits [len(positions), vocab] after `positions` of ONE sequence."""
    return jax.jit(arch.logits_at, static_argnames=("config",))(
        params, jnp.asarray(tokens, jnp.int32), jnp.asarray(positions, jnp.int32), config=Frozen(config))


def window_sample(timeline: Sequence[Mapping[str, Any]], seed: int, n: int) -> List[Mapping[str, Any]]:
    """Of the window's requests that finished (every token asked for came,
    no error), the longest by prompt + served tokens and `n` - 1 others
    drawn from the seed; all of them where fewer finished."""
    done = [r for r in timeline if r["counted"] and r["error"] is None and r["tokens"] and len(r["tokens"]) == r["max_new_tokens"]]
    if len(done) <= n:
        return done
    longest = max(done, key=lambda r: (r["prompt_tokens"] + len(r["tokens"]), -r["idx"]))
    return [longest] + random.Random(seed).sample([r for r in done if r is not longest], n - 1)


def served_margins(arch, params, prompt: Sequence[int], served: Sequence[int], config: Dict[str, Any],
                   pad_tokens: int, pad_served: int, control: bool = False) -> Dict[str, List[float]]:
    """One pass of the reference over ONE request's prompt with its served
    tokens, teacher-forced. `margins`: for every served token (the first
    from prefill, the others each from a decode step through the paged
    cache), the reference's best logit at that position minus its logit of
    the token that was served. Tokens and positions are padded to
    `pad_tokens` and `pad_served`, so that every request of a cell runs the
    one compiled reference (causal: what follows a position does not reach it).

    With `control`, also what the reference in the precision below would
    have served in the program's place: at the same positions of the same
    tokens, the margin of the token that `in_fp8`'s weights put first."""
    n, k = len(prompt), len(served)
    if n + k > pad_tokens or k > pad_served:
        raise ValueError(f"a request of {n} + {k} tokens does not fit the reference's {pad_tokens} tokens, {pad_served} served")
    tokens, positions, tok = np.zeros(pad_tokens, np.int32), np.full(pad_served, n - 1, np.int32), np.zeros(pad_served, np.int32)
    tokens[: n + k] = list(prompt) + list(served)
    positions[:k] = n - 1 + np.arange(k)
    tok[:k] = served
    logits = reference_logits(arch, params, tokens, positions, config)
    best = jnp.max(logits, axis=-1)

    def below_best(t):
        return [float(x) for x in (best - jnp.take_along_axis(logits, t[:, None], axis=-1)[:, 0])[:k]]

    out = {"margins": below_best(jnp.asarray(tok))}
    if control:
        low = reference_logits(arch, jax.jit(in_fp8)(params), tokens, positions, config)
        out["control"] = below_best(jnp.argmax(low, axis=-1))
    return out


def logit_relative_errors(z, z_ref):
    """z, z_ref [n, vocab] -> [n]: ||z_t - zref_t||_2 / ||zref_t||_2, in float32."""
    z, z_ref = z.astype(jnp.float32), z_ref.astype(jnp.float32)
    return jnp.linalg.norm(z - z_ref, axis=-1) / jnp.linalg.norm(z_ref, axis=-1)


def error_quantiles(errors) -> Dict[str, float]:
    """The quantiles a limit may name, and how many positions they are over:
    what a run's `facts` line prints whatever the verdict."""
    e = np.asarray(errors, np.float64).reshape(-1)
    return dict({name: float(np.quantile(e, q)) for name, q in QUANTILES.items()}, positions=int(e.size))


def judge(quantiles: Mapping[str, float], tolerance: Mapping[str, float]) -> bool:
    """`tolerance` is a map from quantile name to limit, e.g. the traffic
    file's `correctness.served_margin_tolerance` {"q100": 0.1}. Every named quantile
    must be at or under its limit (a NaN is over it); naming none is refused."""
    if not tolerance or set(tolerance) - set(QUANTILES):
        raise ValueError(f"a tolerance names quantiles of {sorted(QUANTILES)}, at least one; got {dict(tolerance)}")
    return all(quantiles[name] <= float(limit) for name, limit in tolerance.items())


def compared_quantiles(prefix: str, groups: Sequence[Mapping[str, float]], tolerance: Mapping[str, float]) -> Dict[str, List[float]]:
    """{"<prefix>_<quantile>": [the worst group's reading, its limit]} for
    each quantile the tolerance names: what a run prints beside its verdict."""
    return {f"{prefix}_{name}": [max(g[name] for g in groups), float(limit)] for name, limit in tolerance.items()}


def _is_norm(path) -> bool:
    return "norm" in jax.tree_util.keystr(path)


def draw_norm_scales(params, key):
    """Every norm leaf drawn from U[0.5, 1.5], in the leaf's dtype; every
    other leaf as it is. Shapes do not change, so no program does."""
    leaves, treedef = jax.tree_util.tree_flatten_with_path(params)
    drawn = [
        jax.random.uniform(jax.random.fold_in(key, i), leaf.shape, jnp.float32, 0.5, 1.5).astype(leaf.dtype)
        if _is_norm(path) else leaf
        for i, (path, leaf) in enumerate(leaves)
    ]
    return jax.tree_util.tree_unflatten(treedef, drawn)


def init_weights(tfm, cfg, key):
    """The weights of a run, as both workers and the builder's tool make
    them (inside one jit): the program's init, then the norm scales drawn."""
    return draw_norm_scales(tfm.init_params(key, cfg), jax.random.fold_in(key, 2))


def in_fp8(params):
    """Every weight matrix rounded to 3 mantissa bits at bfloat16's exponent
    range: what an fp8 path with well-chosen scales keeps. Not
    astype(float8).astype(bf16): under jit XLA drops that pair
    (xla_allow_excess_precision)."""
    return jax.tree_util.tree_map_with_path(
        lambda path, a: a if _is_norm(path) else jax.lax.reduce_precision(a, exponent_bits=8, mantissa_bits=3), params)


# ------------------------------------------------- a reference that trains

# optax.adamw's defaults; lib/worker_train.py passes them to the program's optimizer by name, so the two cannot part.
ADAMW = {"b1": 0.9, "b2": 0.999, "eps": 1e-8, "weight_decay": 1e-4}
F32 = jnp.float32


def leaf_norms(leaves) -> jax.Array:
    """[n] float32: the L2 norm of each leaf, in the order given."""
    return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(x.astype(F32)))) for x in leaves])


def change_norms(new, old) -> jax.Array:
    """[leaves] float32: the norm of each leaf's change, in float32."""
    return leaf_norms([a.astype(F32) - b.astype(F32) for a, b in zip(jax.tree_util.tree_leaves(new), jax.tree_util.tree_leaves(old))])


def first_moment(opt_state) -> List[jax.Array]:
    """Adam's first moment out of an optax state, leaf by leaf in the
    parameters' own order. train/zero.py keeps it as flat shards keyed by
    the leaf's number (padding is zero, so a shard's norm is the leaf's)."""
    (adam,) = [s for s in jax.tree_util.tree_leaves(opt_state, is_leaf=lambda s: hasattr(s, "mu")) if hasattr(s, "mu")]
    mu = adam.mu
    if isinstance(mu, dict) and all(str(k).isdigit() for k in mu):
        return [mu[k] for k in sorted(mu, key=int)]
    return jax.tree_util.tree_leaves(mu)


def training_reference(arch, config: Dict[str, Any], mesh, lr: float, steps: int) -> Callable:
    """-> run(make_params, tokens) -> {"losses": [steps], "grad_norms": [leaves], "change_norms": [leaves]}.

    The plain trainer: the mean of the architecture file's `sequence_nll`
    over the whole batch, one sequence at a time, differentiated by
    `jax.grad`; each chip of the mesh takes its own rows and the mean over
    the chips is the batch's. Then adamw as published (Loshchilov & Hutter;
    bias-corrected moments, decay decoupled), arithmetic in float32, state
    and parameters stored in the parameters' own type, which is what the
    configuration states (bfloat16, no master copy: at the files' learning
    rate a parameter moves by whole units of its last place or not at all,
    and the reference has to round where the configuration does).
    `grad_norms` are the first step's; `change_norms` the parameters' after
    the last step less those it was given."""
    from jax.sharding import PartitionSpec as P

    b1, b2, eps, wd = (ADAMW[k] for k in ("b1", "b2", "eps", "weight_decay"))

    def batch_loss(p, toks):
        return jnp.mean(jax.lax.map(jax.checkpoint(lambda s: arch.sequence_nll(p, s, config)), toks))

    def step(p, m, v, t, toks):
        loss, g = jax.lax.pmean(jax.value_and_grad(batch_loss)(p, toks), "data")

        def leaf(p, m, v, g):
            p32, g32 = p.astype(F32), g.astype(F32)
            m32 = b1 * m.astype(F32) + (1 - b1) * g32
            v32 = b2 * v.astype(F32) + (1 - b2) * g32 * g32
            update = (m32 / (1 - b1**t)) / (jnp.sqrt(v32 / (1 - b2**t)) + eps) + wd * p32
            return (p32 - lr * update).astype(p.dtype), m32.astype(m.dtype), v32.astype(v.dtype)

        treedef = jax.tree_util.tree_structure(p)
        new = [leaf(*x) for x in zip(*(jax.tree_util.tree_leaves(a) for a in (p, m, v, g)))]
        p, m, v = (jax.tree_util.tree_unflatten(treedef, [x[i] for x in new]) for i in range(3))
        return p, m, v, loss, leaf_norms(jax.tree_util.tree_leaves(g))

    step = jax.jit(jax.shard_map(step, mesh=mesh, in_specs=(P(), P(), P(), P(), P("data")), out_specs=P(), check_vma=False),
                   donate_argnums=(0, 1, 2))
    change = jax.jit(change_norms)

    def run(make_params, tokens):
        p = make_params()  # made anew and again for the change: held through the steps they would be a fifth copy beside p, m, v, g
        zeros = jax.jit(lambda p: jax.tree_util.tree_map(jnp.zeros_like, p))
        m, v = zeros(p), zeros(p)
        losses, grad_norms = [], None
        for t in range(1, steps + 1):
            p, m, v, loss, norms = step(p, m, v, jnp.float32(t), tokens)
            losses.append(float(loss))
            grad_norms = norms if grad_norms is None else grad_norms
        del m, v
        return {"losses": losses, "grad_norms": np.asarray(grad_norms, np.float64),
                "change_norms": np.asarray(change(p, make_params()), np.float64)}

    run.step = step  # benchmarks/rehearse.py aot compiles it for the chip: does the reference fit
    return run


def norm_gaps(program, reference, count=None) -> np.ndarray:
    """Leaf by leaf, the gap between the program's norm and the reference's
    (not the norm of their difference), against the reference's norm of that
    leaf or of the median leaf, whichever is larger: some gradients are all
    but zero. `count` [leaves] bool leaves leaves out (their gap reads 0)."""
    program, reference = np.asarray(program, np.float64), np.asarray(reference, np.float64)
    gaps = np.abs(program - reference) / np.maximum(reference, np.median(reference))
    return gaps if count is None else np.where(count, gaps, 0.0)


def moved_by_the_gradient(reference_grad_norms) -> np.ndarray:
    """[leaves] bool: the leaves whose change is compared. A leaf whose
    gradient is nought to rounding in the reference (under a thousandth of
    the median leaf's) moves under adam by round-off alone."""
    g = np.asarray(reference_grad_norms, np.float64)
    return g >= 1e-3 * np.median(g)


def compare_training(program: Mapping[str, Any], reference: Mapping[str, Any], tolerance: Mapping[str, float],
                     names: Sequence[str]):
    """-> (compared {name: [value, limit]}, facts). `program` and `reference`
    hold what `training_reference` returns; `tolerance` is the traffic file's
    `correctness`. Each step's loss by its distance; both norms by the worst
    leaf's gap (`norm_gaps`), the change over the leaves that
    `moved_by_the_gradient` counts."""
    moved = moved_by_the_gradient(reference["grad_norms"])
    grad = norm_gaps(program["grad_norms"], reference["grad_norms"])
    change = norm_gaps(program["change_norms"], reference["change_norms"], moved)
    compared = {f"loss_step{i + 1}": [abs(a - b), float(tolerance["loss_abs_tolerance"])]
                for i, (a, b) in enumerate(zip(program["losses"], reference["losses"]))}
    compared["grad_norm_gap"] = [float(grad.max()), float(tolerance["grad_norm_gap_tolerance"])]
    compared["param_change_gap"] = [float(change.max()), float(tolerance["param_change_gap_tolerance"])]
    facts = {
        "leaves": len(names), "leaves_not_counted_in_change": [n for n, ok in zip(names, moved) if not ok],
        "grad_norm_gap": {"worst_leaf": names[int(grad.argmax())], "median": float(np.median(grad))},
        "param_change_gap": {"worst_leaf": names[int(change.argmax())], "median": float(np.median(change[moved]))},
        "reference_median_leaf": {"grad_norm": float(np.median(reference["grad_norms"])), "change_norm": float(np.median(reference["change_norms"]))},
    }
    return compared, facts
