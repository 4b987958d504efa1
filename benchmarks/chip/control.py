"""Readings that set a cell's limits of ``correct``, beside the program's.

    python3 benchmarks/chip/control.py --workload danube4l.k1.t256 \
        --seeds 1,2,3

On each seed, at the cell's own size and on a canonical schedule of
three updates (each worker's next batch at the newest version, K rows an
update), the plain reference is put in the program's place in three
ways, and each is compared with the float32 reference as a run is:

* ``control``: one precision below the configuration's bfloat16, every
  product's operands rounded to fp8 (e4m3) on the way in, activations
  and cotangents bfloat16;
* ``half_batch``: the gradient over half of each batch's rows;
* ``altered``: each gradient's first leaf doubled where it is produced.

A step that leaves the state unchanged reads 1 by the measure and needs
no run.  One JSON line per seed and stand-in.  The benchmark's own runs
never run this; it needs the chip.
"""
import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(HERE)),
                                "src"))


def schedule(workers: int, k: int):
    """Updates of ``k`` gradients; gradient i of worker w is its i-th,
    taken at the newest version when it starts."""
    out, seqs = [], [0] * workers
    for v in range(3):
        members = []
        for j in range(k):
            w = (v * k + j) % workers
            seqs[w] += 1
            members.append((w, seqs[w], v))
        out.append(members)
    return out


def readings(c, seed: int):
    import jax
    import jax.numpy as jnp

    import check
    import gen
    import harness
    from reference import Ref
    from repro.models import model as M

    conf, tr = c["config"], c["traffic"]
    cfg = harness.model_config(conf)
    tmpl = jax.eval_shape(lambda: M.init_params(jax.random.PRNGKey(0), cfg))
    w0 = gen.weights(seed, tmpl)
    x, y, _, _ = gen.token_rows(seed, tr, cfg.vocab_size)
    W, B, lr = tr["workers"], tr["batch"], tr["lr"]
    k = int(tr["schedule"].split(":")[1])
    sched = schedule(W, k)
    feeds = [gen.worker_batches(x, y, w, W, B, seed) for w in range(W)]
    drawn = {}
    for members in sched:
        for w, s, _ in members:
            drawn[(w, s)] = next(feeds[w])
    batch_of = lambda w, s: drawn[(w, s)]                  # noqa: E731
    wire = jnp.bfloat16 if tr["slab_dtype"] == "bf16" else jnp.float32
    f32 = Ref(conf["shape"])
    ref = check.replay(f32.grad, w0, sched, batch_of, lr, wire)

    def half(p, xb, yb):
        n = xb.shape[0] // 2
        return f32.grad(p, xb[:n], yb[:n])

    def altered(p, xb, yb):
        loss, g = f32.grad(p, xb, yb)
        first = jax.tree_util.tree_leaves(g)[0]
        leaves, treedef = jax.tree_util.tree_flatten(g)
        return loss, jax.tree_util.tree_unflatten(treedef,
                                                  [first * 2] + leaves[1:])

    stand_ins = {
        "control": Ref(conf["shape"], dtype=jnp.bfloat16,
                       operand_dtype=jnp.float8_e4m3fn).grad,
        "half_batch": half,
        "altered": altered,
    }
    for name, grad in stand_ins.items():
        t = time.time()
        try:
            got = check.replay(grad, w0, sched, batch_of, lr, wire)
        except Exception as e:      # a control that crashes has failed
            yield {"seed": seed, "stand_in": name, "error": repr(e)}
            continue
        prog = {"update1": got["grad1"] * lr, "change3": got["change3"]}
        v = check.verdict(prog, ref, lr, c["limits"])
        yield {"seed": seed, "stand_in": name,
               **{n: r["value"] for n, r in v.items()},
               "seconds": round(time.time() - t, 1)}


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated, e.g. 1,2,3")
    args = ap.parse_args(argv)
    import harness

    c = harness.cell(args.workload)
    harness.device_info(c["entry"]["chips"], require_tpu=True)
    harness.use_cache()
    for seed in (int(s) for s in args.seeds.split(",")):
        for row in readings(c, seed):
            print(json.dumps(row), flush=True)


if __name__ == "__main__":
    main()
