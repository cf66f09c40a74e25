"""Builder's tool: a decoder cell's fused step compiled ahead of time
for a described (not attached) v5e chip, to size a batch by the cells'
13.5 GiB rule without chip time.

    python3 benchmark/tools/aot_step.py <cell> <batch> [seq] [dump dir]

The step is the one ``ray_tpu/train/operator.py`` fuses (loss, gradients,
optimizer update, donated state) on shapes from ``jax.eval_shape`` of the
family's ``model_init``; the kernels are steered to Mosaic (here
``jax.default_backend()`` is the CPU). Prints ``memory_analysis``'
arguments + temporaries and the buffer assignment's "Total bytes used"
(the two part by gigabytes on some programs: read both; the chip has
borne out the second), the Mosaic calls in the compiled text and the
parameter count. Run such compiles one after another: two at once fight
over libtpu's lock file. A compile that passes is not a chip run."""

import glob
import os
import re
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ["JAX_PLATFORMS"] = "cpu"
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

import jax  # noqa: E402
from jax.experimental import topologies  # noqa: E402
from jax.sharding import SingleDeviceSharding  # noqa: E402

from benchmark import manifest  # noqa: E402
from ray_tpu.ops import (attention, batchnorm, layernorm, moe_gmm,  # noqa: E402
                         short_conv, ssd)

GiB = 2 ** 30


def main():
    for mod in (attention, batchnorm, layernorm, moe_gmm, short_conv, ssd):
        mod.is_tpu = lambda: True
    jax.config.update("jax_enable_compilation_cache", False)
    name, batch = sys.argv[1], int(sys.argv[2])
    cell = manifest.cell(name)
    workload = dict(cell["workload"], batch=batch)
    rest = sys.argv[3:]
    if rest and rest[0].isdigit():
        workload["seq"] = int(rest.pop(0))
    dump = rest[0] if rest else os.path.join(
        os.environ.get("TMPDIR", "/tmp"), f"aot_{name}_{batch}")
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])
    p = cell["family"].pieces(cell["model"], workload, 7)
    params, state = jax.eval_shape(p.model_init, jax.random.key(0))
    opt = jax.eval_shape(p.optimizer.init, params)

    def on_chip(tree):
        return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=one), tree)

    def fused(params, mstate, opt_state, batch):
        with jax.named_scope("forward_backward"):
            (loss, mstate), grads = jax.value_and_grad(
                p.loss_fn, has_aux=True)(params, mstate, batch)
        with jax.named_scope("optimizer"):
            updates, opt_state = p.optimizer.update(grads, opt_state, params)
            params = jax.tree.map(lambda a, u: a + u, params, updates)
        return params, mstate, opt_state, loss

    t0 = time.time()
    compiled = jax.jit(fused, donate_argnums=(0, 1, 2)).lower(
        on_chip(params), on_chip(state), on_chip(opt),
        on_chip(p.batch)).compile(compiler_options={
            "xla_dump_to": dump, "xla_dump_hlo_as_text": True})
    m = compiled.memory_analysis()
    print(f"{name} batch {batch} seq {workload.get('seq')}: compiled in "
          f"{time.time() - t0:.0f} s; dump in {dump}")
    print(f"  memory_analysis: arguments {m.argument_size_in_bytes / GiB:.2f}"
          f" + temporaries {m.temp_size_in_bytes / GiB:.2f} = "
          f"{(m.argument_size_in_bytes + m.temp_size_in_bytes) / GiB:.2f} GiB")
    for report in glob.glob(dump + "/*memory-usage-report.txt"):
        total = re.search(r"Total bytes used: (\d+)", open(report).read())
        if total:
            print("  buffer assignment, Total bytes used: "
                  f"{int(total.group(1)) / GiB:.2f} GiB")
    print("  Mosaic calls in the text:",
          compiled.as_text().count("tpu_custom_call"))
    print("  parameters:", sum(x.size for x in jax.tree.leaves(params)))


if __name__ == "__main__":
    main()
