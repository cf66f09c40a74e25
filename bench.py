"""Headline benchmark: ResNet-50 synthetic-data training throughput
THROUGH THE FRAMEWORK — Trainer + TrainingOperator, with the train step
running inside a TPU-designated worker actor, weights/metrics moving over
the object store. Mirrors the reference, whose headline number also runs
through its trainer (reference:
python/ray/util/sgd/torch/torch_trainer.py:365 and
python/ray/util/sgd/torch/examples/benchmarks/README.rst:146-153 —
ResNet-50, synthetic ImageNet, batch 128/device, 352.5 img/s per V100).

The inner step is a single fused jit: bfloat16 NHWC convs on the MXU,
fp32 SGD+momentum update, donated buffers, loss kept on device (no host
sync inside the epoch). A raw-jit control run measures the same step
without the framework so framework overhead is reported, not assumed.

Needs a TPU: with no chip it exits non-zero and prints no number (no
CPU attempt, no cached replay). The supervisor process never touches a
JAX backend — the chip belongs to the child that computes.

Prints ONE JSON line:
    {"metric": ..., "value": N, "unit": "img/s/chip", "vs_baseline": N,
     "raw_jit_img_s": N, "framework_fraction": N, "batch": N,
     "device": {"platform": ..., "kind": ..., "count": N}}
"""

import json
import os
import subprocess
import sys
import time

BASELINE_IMG_S = 352.5  # reference: V100 img/s/GPU (BASELINE.md)
BATCH = 256             # per-chip batch (sweep result: see PERF.md)
STEPS = 30


def _bench_config():
    # RAY_TPU_BENCH_STEM=s2d flips the exactly-equivalent space-to-depth
    # stem (models/resnet.py); read once here so the raw child and the
    # framework worker provably use the same value
    stem = os.environ.get("RAY_TPU_BENCH_STEM", "standard")
    if stem not in ("standard", "s2d"):
        raise ValueError(f"RAY_TPU_BENCH_STEM={stem!r}: expected "
                         "'standard' or 's2d'")
    # RAY_TPU_BENCH_BN=pallas swaps the BN training backward for the
    # fused dual-reduction kernel (ops/batchnorm.py); same math
    bn = os.environ.get("RAY_TPU_BENCH_BN", "xla")
    if bn not in ("xla", "pallas"):
        raise ValueError(f"RAY_TPU_BENCH_BN={bn!r}: expected "
                         "'xla' or 'pallas'")
    return {"model": "resnet50", "batch": BATCH, "hw": 224,
            "steps": STEPS, "stem": stem, "bn": bn}


# ---------------------------------------------------------------------------
# shared model/step construction
# ---------------------------------------------------------------------------

def _make_batch(cfg_dict):
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import resnet

    cfg = (resnet.resnet50(stem_mode=cfg_dict.get("stem", "standard"),
                           bn_mode=cfg_dict.get("bn", "xla"))
           if cfg_dict["model"] == "resnet50"
           else resnet.resnet18(num_classes=10, small_images=True))
    key = jax.random.key(0)
    images = jax.random.normal(
        key, (cfg_dict["batch"], cfg_dict["hw"], cfg_dict["hw"], 3),
        jnp.bfloat16)
    labels = jax.random.randint(key, (cfg_dict["batch"],), 0,
                                cfg.num_classes)
    return cfg, (images, labels)


class _Repeat:
    """Synthetic loader: yields the same device-resident batch N times."""

    def __init__(self, batch, n):
        self.batch, self.n = batch, n

    def __iter__(self):
        for _ in range(self.n):
            yield self.batch


def _operator_cls():
    from ray_tpu.train import TrainingOperator

    class Op(TrainingOperator):
        def setup(self, config):
            import optax

            from ray_tpu.models import resnet

            cfg, batch = _make_batch(config)
            self.register(
                model_init=lambda key: resnet.init(key, cfg),
                loss_fn=lambda p, s, b: resnet.loss_fn(
                    p, s, b[0], b[1], cfg),
                optimizer=optax.sgd(0.1, momentum=0.9),
                stateful=True)
            self.register_data(
                train_loader=_Repeat(batch, config["steps"] + 4))

        def train_epoch(self, num_steps=None, profile_dir=None):
            # the driver never touches a JAX backend: device facts are
            # read here, in the chip-owning actor, and ride the result
            out = super().train_epoch(num_steps, profile_dir=profile_dir)
            out["device"] = _device_facts()
            return out

    return Op


def _device_facts() -> dict:
    import jax

    dev = jax.devices()[0]
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": jax.device_count()}


# ---------------------------------------------------------------------------
# framework path (the headline)
# ---------------------------------------------------------------------------

def run_framework():
    cfg = _bench_config()
    import ray_tpu
    from ray_tpu.train import Trainer

    ray_tpu.init(num_cpus=4)
    trainer = Trainer(_operator_cls(), num_workers=1, config=cfg,
                      use_tpu=True)
    trainer.train(num_steps=3)  # compile + warmup
    result = trainer.train(num_steps=cfg["steps"])
    trainer.shutdown(force=True)
    ray_tpu.shutdown()
    print(json.dumps({"_framework_img_s": result["samples_per_s"],
                      "batch": cfg["batch"],
                      "device": result["device"]}))


# ---------------------------------------------------------------------------
# raw-jit control (framework overhead denominator)
# ---------------------------------------------------------------------------

def _raw_step(cfg_d):
    """The operator's step with no framework around it:
    (jitted step, [params, state, opt_state], batch)."""
    import jax

    import optax

    from ray_tpu.models import resnet

    cfg, batch = _make_batch(cfg_d)
    params, state = resnet.init(jax.random.key(0), cfg)
    opt = optax.sgd(0.1, momentum=0.9)

    def step(params, state, opt_state, batch):
        (loss, new_state), grads = jax.value_and_grad(
            resnet.loss_fn, has_aux=True)(
                params, state, batch[0], batch[1], cfg)
        updates, opt_state = opt.update(grads, opt_state, params)
        params = jax.tree.map(lambda p, u: p + u, params, updates)
        return params, new_state, opt_state, loss

    return (jax.jit(step, donate_argnums=(0, 1, 2)),
            [params, state, opt.init(params)], batch)


def run_raw():
    from ray_tpu._private import compile_cache

    compile_cache.enable_persistent_cache()
    import jax

    cfg_d = _bench_config()
    step, carry, batch = _raw_step(cfg_d)
    for _ in range(3):
        *carry, loss = step(*carry, batch)
    jax.block_until_ready(loss)

    t0 = time.perf_counter()
    for _ in range(cfg_d["steps"]):
        *carry, loss = step(*carry, batch)
    jax.block_until_ready(loss)
    dt = time.perf_counter() - t0
    print(json.dumps({"_raw_img_s": cfg_d["batch"] * cfg_d["steps"] / dt,
                      "device": _device_facts()}))


# ---------------------------------------------------------------------------
# supervisor
# ---------------------------------------------------------------------------

def _run_child(mode, timeout, expect):
    """One measurement in a child process: the supervisor stays off JAX
    (a parent that touched it would hold the chip)."""
    try:
        out = subprocess.run(
            [sys.executable, os.path.abspath(__file__), mode],
            timeout=timeout, capture_output=True, text=True)
    except subprocess.TimeoutExpired:
        return None
    for line in (out.stdout or "").splitlines():
        if line.startswith("{"):
            try:
                d = json.loads(line)
            except json.JSONDecodeError:
                continue
            if expect in d:
                return d
    sys.stderr.write((out.stdout or "")[-2000:] + (out.stderr or "")[-2000:])
    return None


def _supervise() -> int:
    _bench_config()  # fail fast on bad knobs
    from ray_tpu.api import _detect_tpu_chips

    if not _detect_tpu_chips():
        sys.stderr.write("bench.py: this machine exposes no TPU chip; "
                         "nothing measured\n")
        return 1
    fw = _run_child("--inner-framework", 900, "_framework_img_s")
    raw = _run_child("--inner-raw", 900, "_raw_img_s")
    if fw is None or raw is None:
        sys.stderr.write("bench.py: a measurement failed; nothing "
                         "reported\n")
        return 1
    for d in (fw, raw):
        if d["device"]["platform"] != "tpu":
            sys.stderr.write(f"bench.py: ran on {d['device']}, not a "
                             "TPU; nothing reported\n")
            return 1
    img_s, raw_img_s = fw["_framework_img_s"], raw["_raw_img_s"]
    print(json.dumps({
        "metric": "resnet50_train_img_s_per_chip",
        "value": round(img_s, 1),
        "unit": "img/s/chip",
        "vs_baseline": round(img_s / BASELINE_IMG_S, 3),
        "raw_jit_img_s": round(raw_img_s, 1),
        "framework_fraction": round(img_s / raw_img_s, 3),
        "batch": fw["batch"],
        "device": fw["device"],
    }))
    return 0


if __name__ == "__main__":
    if "--inner-framework" in sys.argv:
        run_framework()
    elif "--inner-raw" in sys.argv:
        run_raw()
    else:
        sys.exit(_supervise())
