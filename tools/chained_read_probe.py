"""Does a step's result come back when ITS execution ends, with a newer one launched behind it?

Two chained executions of one jitted step (the second takes the first's output as an operand, as
PagedLM's chained decode does), the first's result read after the second's dispatch: with
`copy_to_host_async()` asked for at each launch, and without. Prints the reads' instants against
one step's duration, then the period of a loop that keeps two in flight.

    chiprun -- python3 tools/chained_read_probe.py
"""

import json
import time

import jax
import jax.numpy as jnp
import numpy as np


@jax.jit
def step(w, prev):
    x = w
    for _ in range(24):  # ~10 ms of matmuls on a v5e
        x = jnp.tanh(x @ w)
    out = (jnp.argmax(x[:64], axis=-1).astype(jnp.int32) + prev) % 1000
    return out


def chained(w, prev, ask: bool):
    t0 = time.monotonic()
    a = step(w, prev)
    if ask:
        a.copy_to_host_async()
    b = step(w, a)
    if ask:
        b.copy_to_host_async()
    t_launched = time.monotonic()
    np.asarray(a)
    t_a = time.monotonic()
    np.asarray(b)
    t_b = time.monotonic()
    return {"launched_ms": (t_launched - t0) * 1e3, "first_read_ms": (t_a - t0) * 1e3, "second_read_ms": (t_b - t0) * 1e3}


def loop(w, prev, ask: bool, n: int = 60):
    """Keeps two in flight: launch k+1, then read k. The median distance between two reads."""
    flying = step(w, prev)
    if ask:
        flying.copy_to_host_async()
    reads = []
    for _ in range(n):
        nxt = step(w, flying)
        if ask:
            nxt.copy_to_host_async()
        np.asarray(flying)
        reads.append(time.monotonic())
        flying = nxt
    np.asarray(flying)
    return float(np.median(np.diff(reads)) * 1e3)


def main():
    w = jax.random.normal(jax.random.PRNGKey(0), (4096, 4096), jnp.bfloat16) * 0.02
    prev = jax.device_put(np.zeros((64,), np.int32))
    np.asarray(step(w, prev))  # compile
    alone = []
    for _ in range(10):
        t0 = time.monotonic()
        np.asarray(step(w, prev))
        alone.append((time.monotonic() - t0) * 1e3)
    report = {"device": jax.devices()[0].device_kind, "one_step_and_read_ms": float(np.median(alone))}
    for ask in (True, False):
        runs = [chained(w, prev, ask) for _ in range(10)]
        key = "copy_asked_at_launch" if ask else "copy_at_read"
        report[key] = {k: float(np.median([r[k] for r in runs])) for k in runs[0]}
        report[key]["loop_read_period_ms"] = loop(w, prev, ask)
    print(json.dumps(report, indent=1))


if __name__ == "__main__":
    main()
