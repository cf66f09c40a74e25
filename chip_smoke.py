"""chip_smoke.py — the quickest proof that ray_tpu still starts on the chip.

Drives the headline path once, through the entry points a user calls:

    ray_tpu.init() -> Trainer(Op, num_workers=1, use_tpu=True)
      -> TrainWorker actor in the TPU-flavour worker (the one process
         that owns the chip) -> TrainingOperator's fused jitted step

at the full width of two models the repo supports, with random weights
made from a seed:

    python chip_smoke.py            one chip: ResNet-50 (batch 256, 224x224,
                                    bf16 NHWC, SGD+momentum) and GPT-2-small
                                    (12 layers, d 768, seq 1024, batch 8),
                                    then one warm restart of each
    python chip_smoke.py --chips 4  ONLY the four-chip phases and what they
                                    are compared with: GPT-2-small, then
                                    GPT-2-large cut to 6 layers (every width
                                    as published), over one worker holding
                                    {"TPU": 4} — the Trainer derives the
                                    (data=1, fsdp=4) mesh from that lease:
                                    parameters, optimizer state and batch
                                    sharded four ways — against the same
                                    steps on one device of that process

This driver process never initialises a JAX backend: device facts are
read inside the chip-owning actor and ride the results back. Every phase
prints one JSON line; any failed check raises, so the exit code is
non-zero and no ``ok`` is printed. With no chip (or on a machine where
the TPU resource does not resolve to a TPU backend) the script fails.
The last line of a passing run is exactly

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}
"""

from __future__ import annotations

import argparse
import json
import math
import sys
import time

RESNET50 = {"model": "resnet50", "batch": 256, "hw": 224,
            "stem": "standard", "bn": "xla"}
GPT2_SMALL = {"model": "gpt2_small", "batch": 8, "seq": 1024}
# GPT-2-large at a depth one chip holds beside its AdamW state (the
# benchmark's gpt2_large is 36 layers: 12.4 GB before one activation)
GPT2_LARGE_CUT = {"model": "gpt2_large_6l", "batch": 8, "seq": 1024}
# one-device-vs-mesh loss agreement over the first three steps: same
# global batch, same seed. The step-0 loss sees the forward pass only;
# steps 1 and 2 see the gradients the collectives combined, through
# AdamW's update (a gradient from a quarter of the batch, or a sum
# missed over a shard, moves them by percents — AdamW takes steps of
# the same size whatever the gradient's scale, so a wrong constant
# factor alone would not show here; the CPU tests compare the gradients
# themselves with the float32 reference). bf16 matmuls reduce in
# another order across four devices: measured up to 8.7e-6 on the chip
# (GPT-2-small 2.0e-6 / 8.7e-6, the cut GPT-2-large 5.5e-6 / 7.4e-6 at
# steps 1 / 2, step 0 bit-identical: my chip run, PR 27), up to 2e-5 at
# the tiny size on the CPU.
LOSS_RTOL = 2e-4


# ---------------------------------------------------------------------------
# operators (run inside the chip-owning actor)
# ---------------------------------------------------------------------------

def _gpt_pieces(size: dict):
    """(model_init, loss_fn, optimizer, batch) of the GPT phase — shared
    by the operator and the one-device comparison, so both run the same
    program on the same tokens."""
    import jax
    import optax

    from ray_tpu.models import transformer

    cfg = {"gpt2_small": transformer.GPT2_SMALL,
           "gpt2_large_6l": transformer.TransformerConfig(
               n_layers=6, n_heads=20, d_model=1280, d_ff=5120),
           "tiny": transformer.TINY}[size["model"]]
    tokens = jax.random.randint(jax.random.key(size["seed"]),
                                (size["batch"], size["seq"]), 0,
                                cfg.vocab_size)
    return (lambda key: transformer.init(key, cfg),
            lambda p, b: transformer.loss_fn(p, b, cfg),
            optax.adamw(3e-4), tokens)


class _Measured:
    """Mixin over a TrainingOperator: every step is its own epoch closed
    on block_until_ready, and the result carries what only the
    chip-owning process can read — the device, the kernels and
    collectives in the compiled step, JAX's persistent-cache counters,
    per-device memory."""

    def __init__(self, *args, **kwargs):
        import jax

        self._cache_events = {"hits": 0, "misses": 0}
        self._programs = 0  # compiled, or loaded from JAX's cache

        def count(event, **_):
            if event == "/jax/compilation_cache/cache_hits":
                self._cache_events["hits"] += 1
            elif event == "/jax/compilation_cache/cache_misses":
                self._cache_events["misses"] += 1

        def count_program(event, duration, **_):
            # JAX times the compile-or-load of every new program
            if event == "/jax/core/compile/backend_compile_duration":
                self._programs += 1

        # before setup(): the first compile is the model's init
        jax.monitoring.register_event_listener(count)
        jax.monitoring.register_event_duration_secs_listener(count_program)
        super().__init__(*args, **kwargs)

    def train_epoch(self, num_steps=None, profile_dir=None):
        import jax

        from ray_tpu._private import compile_cache

        step_s, losses, programs = [], [], self._programs
        for _ in range(num_steps):
            t0 = time.perf_counter()
            out = super().train_epoch(1)
            jax.block_until_ready((self.params, self.opt_state))
            step_s.append(time.perf_counter() - t0)
            losses.append(out["last_train_loss"])
        programs = self._programs - programs
        if not hasattr(self, "_step_text"):  # lowered and compiled once
            self._step_text = self.compiled_step_text(
                next(iter(self._train_loader)))
        text = self._step_text
        state = jax.tree.leaves((self.params, self.opt_state))
        return {
            "step_s": step_s, "losses": losses,
            # programs these steps had to compile (or load from JAX's
            # persistent cache): none once a step's shapes are warm
            "programs_built": programs,
            "device": _device_facts(),
            # the training state as a whole, and the part of it the
            # layout puts on one device
            "state_bytes": sum(x.nbytes for x in state),
            "state_bytes_per_device": sum(
                x.addressable_shards[0].data.nbytes for x in state),
            "mesh": (None if self._mesh is None
                     else [int(n) for n in self._mesh.shape.values()]),
            "tpu_custom_calls": text.count("tpu_custom_call"),
            "collectives": {k: text.count(k + "(") + text.count(
                k + "-start(") for k in (
                "all-reduce", "all-gather", "reduce-scatter")},
            "jax_cache": dict(self._cache_events,
                              dir=compile_cache.jax_cache_dir()),
            "device_bytes_in_use": [
                (d.memory_stats() or {}).get("bytes_in_use")
                for d in jax.local_devices()],
        }


# ResNet-50 pieces (synthetic batch, operator, raw-jit control); they were
# bench.py's until that file went (PR 29)

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


def _resnet_operator():
    class ResNetSmoke(_Measured, _operator_cls()):
        def validate(self, num_steps=None):
            """Raw-jit control: the same step with no framework around
            it, in THIS process (a second chip-owning process could not
            get the chip)."""
            import jax

            step, carry, batch = _raw_step(self.config)
            step_s, losses = [], []
            for _ in range(1 + num_steps):  # the first one compiles
                t0 = time.perf_counter()
                *carry, loss = step(*carry, batch)
                jax.block_until_ready(carry)
                step_s.append(time.perf_counter() - t0)
                losses.append(float(loss))
            return {"raw_first_step_s": step_s[0],
                    "raw_step_s": step_s[1:], "raw_losses": losses}

    return ResNetSmoke


def _gpt_operator():
    from ray_tpu.train import TrainingOperator

    class GPTSmoke(_Measured, TrainingOperator):
        def setup(self, config):
            model_init, loss_fn, optimizer, tokens = _gpt_pieces(config)
            # no mesh argument: a lease of several chips shards by itself
            self.register(model_init=model_init, loss_fn=loss_fn,
                          optimizer=optimizer, seed=config["seed"])
            self.register_data(
                train_loader=_Repeat(tokens, config["steps"]))

        def validate(self, num_steps=None):
            """The same steps on ONE device of this process (the mesh
            phase's comparison): same seed, same tokens, plain jit."""
            import jax

            model_init, loss_fn, optimizer, tokens = _gpt_pieces(self.config)
            params = model_init(jax.random.key(self.config["seed"]))
            opt_state = optimizer.init(params)

            @jax.jit
            def step(params, opt_state, batch):
                loss, grads = jax.value_and_grad(loss_fn)(params, batch)
                updates, opt_state = optimizer.update(grads, opt_state,
                                                      params)
                return (jax.tree.map(lambda p, u: p + u, params, updates),
                        opt_state, loss)

            losses = []
            for _ in range(num_steps):
                params, opt_state, loss = step(params, opt_state, tokens)
                losses.append(float(loss))
            return {"one_device_losses": losses}

    return GPTSmoke


# ---------------------------------------------------------------------------
# phases (driver side: public API only, no JAX backend)
# ---------------------------------------------------------------------------

def _check(cond, what: str):
    if not cond:
        raise AssertionError(what)


def _finite(xs) -> bool:
    return all(isinstance(x, float) and math.isfinite(x) for x in xs)


def _phase(name: str, operator_cls, size: dict, *, warmup: int, steps: int,
           seed: int, chips: int = 1, compare: int = 0,
           cold: bool = True, **stated) -> dict:
    """One Trainer, one chip-owning worker process: `warmup` steps (the
    first one compiles), `steps` timed steps (none on a warm restart,
    which is only asked how long its first step takes), optionally
    `compare` steps of the phase's in-process control; then the worker
    is killed so the next phase's process can take the chip."""
    from ray_tpu.train import Trainer

    config = dict(size, seed=seed, steps=warmup + steps + 1)
    trainer = Trainer(operator_cls, num_workers=1, config=config,
                      use_tpu=True,  # one TPU unless resources say more
                      resources_per_worker={"CPU": 1, "TPU": chips})
    try:
        warm = trainer.train(num_steps=warmup)
        timed = (trainer.train(num_steps=steps) if steps
                 else dict(warm, step_s=[], losses=[], programs_built=0))
        control = trainer.validate(num_steps=compare) if compare else {}
    finally:
        trainer.shutdown(force=True)
    line = {
        "phase": name, "model": size["model"], "cold": cold,
        "shape": {k: v for k, v in size.items() if k != "model"},
        "warmup_steps": warmup, "steps": steps,
        "first_step_s": warm["step_s"][0],
        "step_s": timed["step_s"],
        "programs_built": {"warmup": warm["programs_built"],
                           "timed": timed["programs_built"]},
        "losses": warm["losses"] + timed["losses"],
        **{k: timed[k] for k in ("device", "tpu_custom_calls",
                                 "collectives", "jax_cache", "state_bytes",
                                 "state_bytes_per_device", "mesh",
                                 "device_bytes_in_use")},
        **{k: v for k, v in control.items() if k != "num_samples"},
        **stated,
    }
    if steps:  # what the first step cost beyond a steady (timed) one
        steady = sorted(timed["step_s"])[steps // 2]
        line["compile_s"] = max(0.0, line["first_step_s"] - steady)
    print(json.dumps(line), flush=True)
    _check(_finite(line["losses"]), f"{name}: non-finite loss")
    return line


def _check_kernels(line: dict, minimum: int):
    """On the chip no main-path kernel gives way to its XLA fallback
    (or runs interpreted, which leaves no custom call) unnoticed."""
    if line["device"]["platform"] == "tpu":
        _check(line["tpu_custom_calls"] >= minimum,
               f"{line['phase']}: expected >= {minimum} Mosaic kernels in "
               f"the compiled step, found {line['tpu_custom_calls']}")


def phase_resnet(size: dict = RESNET50, *, seed: int = 0, warmup: int = 3,
                 steps: int = 5, cold: bool = True) -> dict:
    line = _phase("resnet50", _resnet_operator(), size, warmup=warmup,
                  steps=steps, seed=seed, compare=steps, cold=cold)
    # the repeated batch: SGD must not diverge on it (BN + momentum make
    # single steps noisy, so compare the ends, with slack)
    _check(line["losses"][-1] <= line["losses"][0] * 1.1,
           f"resnet50: loss rose on the repeated batch: {line['losses']}")
    if steps:
        _check(_finite(line["raw_losses"]), "resnet50: raw-jit loss")
    # default BN is XLA's (bn_mode="xla"): no Mosaic kernel is expected
    return line


def phase_gpt(size: dict = GPT2_SMALL, *, seed: int = 0, warmup: int = 2,
              steps: int = 3, cold: bool = True) -> dict:
    line = _phase("gpt2_small", _gpt_operator(), size, warmup=warmup,
                  steps=steps, seed=seed, cold=cold)
    _check(line["losses"][-1] <= line["losses"][0],
           f"gpt2_small: loss rose on the repeated batch: "
           f"{line['losses']}")
    # flash attention + two layernorms per (scanned) block, forward;
    # the rematerialised backward adds more
    _check_kernels(line, 3)
    return line


def phase_mesh(size: dict = GPT2_SMALL, *, chips: int = 4, seed: int = 0,
               warmup: int = 1, steps: int = 2) -> dict:
    line = _phase(size["model"] + "_mesh", _gpt_operator(), size,
                  warmup=warmup, steps=steps, seed=seed, chips=chips,
                  compare=warmup + steps, loss_rtol=LOSS_RTOL)
    # the Trainer's own mesh, derived from the worker's lease
    _check(line["mesh"] == [1, chips],
           f"a lease of {chips} chips gave the mesh {line['mesh']}")
    for mesh_loss, one in zip(line["losses"], line["one_device_losses"]):
        _check(abs(mesh_loss - one) <= LOSS_RTOL * abs(one),
               f"mesh and one-device losses disagree beyond {LOSS_RTOL}: "
               f"{line['losses']} vs {line['one_device_losses']}")
    # sharded parameters are gathered for use, and only the first step
    # compiles (the step hands its state back laid out as it took it)
    _check(line["collectives"]["all-gather"] > 0
           and sum(line["collectives"].values()) > 1,
           f"the compiled mesh step does not gather sharded parameters "
           f"and combine gradients: {line['collectives']}")
    _check(line["programs_built"]["timed"] == 0,
           f"a step after the first one compiled again: "
           f"{line['programs_built']}, {line['step_s']}")
    # the parameters (and optimizer state) really are spread: the layout
    # puts about a quarter of the state on a device, and every device
    # holds about that and no more
    whole, share = line["state_bytes"], line["state_bytes_per_device"]
    _check(share <= 1.1 * whole / chips,
           f"the layout keeps {share} of {whole} state bytes on one device")
    used = line["device_bytes_in_use"]
    if all(b is not None for b in used):  # CPU devices report no stats
        _check(len(used) == chips
               and all(share <= b <= 1.25 * share for b in used),
               f"devices hold {used} bytes, the layout says {share} each")
    _check_kernels(line, 3)
    return line


# ---------------------------------------------------------------------------
# entry point
# ---------------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import ray_tpu
    from ray_tpu.native.store import native_store_available

    # everything generated is built here from committed sources: no C++
    # compiler must be an error, not a silent switch to the Python store
    _check(native_store_available(),
           "the native object store did not build from native/store")
    ray_tpu.init()  # bare: the TPU resource comes from the machine
    try:
        tpus = ray_tpu.cluster_resources().get("TPU", 0)
        print(json.dumps({"phase": "init", "cluster_tpus": tpus,
                          "asked": args.chips}), flush=True)
        _check(tpus >= args.chips,
               f"this machine exposes {tpus} TPU chip(s); "
               f"{args.chips} needed")
        if args.chips == 4:
            lines = [phase_mesh(seed=args.seed),
                     phase_mesh(GPT2_LARGE_CUT, seed=args.seed)]
        else:
            lines = [phase_resnet(seed=args.seed),
                     phase_gpt(seed=args.seed),
                     # warm restarts: a NEW chip-owning process per phase
                     # finds JAX's compile cache populated
                     phase_resnet(seed=args.seed, warmup=1, steps=0,
                                  cold=False),
                     phase_gpt(seed=args.seed, warmup=1, steps=0,
                               cold=False)]
            for line in lines[2:]:
                _check(line["jax_cache"]["hits"] > 0,
                       f"{line['phase']}: warm restart hit nothing in "
                       f"JAX's persistent cache: {line['jax_cache']}")
    finally:
        ray_tpu.shutdown()

    from jax._src import xla_bridge

    _check(not xla_bridge.backends_are_initialized(),
           "the driver process initialised a JAX backend")
    device = lines[0]["device"]
    _check(all(line["device"] == device for line in lines),
           "phases ran on different devices")
    _check(device["platform"] == "tpu", f"not a TPU: {device}")
    _check(device["count"] == args.chips,
           f"the chip-owning worker sees {device['count']} device(s), "
           f"{args.chips} asked for")
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
