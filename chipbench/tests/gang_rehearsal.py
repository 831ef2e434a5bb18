"""A gang cell run on the CPU over four host devices, as a process of its
own (the device count is fixed before JAX starts):

  python chipbench/tests/gang_rehearsal.py <compile cache dir>

Prints one JSON object: how the gang's weights compare with the one-device
tree made from the same seed, what the window's compile counter reads for
programs built again and for one set-up never built, and two runs of
``tests/data``'s gang cell through ``harness.run``: a sound one, and one
whose resizes hand the survivors an empty slot state (the KV hand-off left
out)."""
import json
import sys
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path[:0] = [str(HERE.parent), str(HERE.parent.parent / "src")]
CELL = "smoke-gang.gang-shrink"
CHIPS = 4
SEEDS = {"sound": 2**33 + 21, "lost_handoff": 2**33 + 22}


def lost_handoff(server):
    """After each resize the survivors resume from an empty slot state."""
    import jax
    import jax.numpy as jnp
    resize = server.resize

    def lossy(n):
        rec = resize(n)
        server.engine.cache = jax.tree.map(jnp.zeros_like, server.engine.cache)
        return rec
    server.resize = lossy


def weights(cell, seed):
    """The gang's tree against the one-device tree, and the bytes each
    device holds of it."""
    import jax
    import numpy as np

    import harness
    gang = harness.make_params(cell, seed, CHIPS)
    held = {}
    for leaf in jax.tree.leaves(gang):
        for shard in leaf.addressable_shards:
            held[shard.device.id] = held.get(shard.device.id, 0) + shard.data.nbytes
    one = harness.make_params(cell, seed)
    bits = lambda a: np.asarray(a).view(np.uint16)  # noqa: E731  (bfloat16 leaves)
    same = all(np.array_equal(bits(a), bits(b))
               for a, b in zip(jax.tree.leaves(one), jax.tree.leaves(gang)))
    return {"identical": same, "tree_bytes": sum(leaf.nbytes for leaf in jax.tree.leaves(one)),
            "held_bytes": [held[i] for i in sorted(held)]}


def compile_counts():
    """Set-up builds a program; the window then asks for it again from a
    new jitted function (as a resize's new engine does), before and after an
    event, and for a shape that only the persistent cache holds."""
    import functools

    import jax
    import numpy as np

    import harness

    def scale(x, k):
        return x * k

    def build(n):
        x = np.ones(n, np.float32)        # a host array: the one program is ``scale``
        return jax.jit(functools.partial(scale, k=3.0))(x).block_until_ready()

    build(7)                       # into the persistent cache, before the counter
    out = {}
    with harness._CompileCounter() as c:
        build(5)                   # set-up
        c.on = True
        build(5)
        out["before_event"] = [c.n, c.loads]
        c.event = {"reloads": 0, "reload_s": 0.0}
        build(5)
        out["after_event"] = [c.n, c.loads, c.event["reloads"]]
        build(6)
        out["new_shape"] = [c.n, c.loads, c.event["reloads"]]
        build(7)
        out["cached_shape"] = [c.n, c.loads, c.event["reloads"]]
    return out


def main() -> int:
    from repro.distributed.elastic_serving import ensure_host_devices
    ensure_host_devices(CHIPS)
    import harness
    import spec
    harness.RAMP_S, harness.TRACE_S = 0.5, 1.0
    harness.configure_jax(Path(sys.argv[1]))
    root = HERE / "data"
    bench = {"workloads": [{"name": CELL, "chips": CHIPS}], "per_layer": [],
             "end_to_end": spec.benchmark()["end_to_end"]}
    device = {"platform": "cpu", "kind": "cpu", "count": CHIPS}
    out = {"weights": weights(spec.cell(CELL, root, CHIPS), SEEDS["sound"]),
           "compile_counts": compile_counts()}
    for name, hook in (("sound", None), ("lost_handoff", lost_handoff)):
        out[name] = harness.run(CELL, SEEDS[name], 4.0, False, time.perf_counter(), device,
                                engine_hook=hook, bench=bench, root=root)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
