"""Driver ``train``: the program's train step, timed over whole steps.

The step is the one a ``JaxTrainer`` user runs:
``jax_utils.build_train_step`` over the family's loss
(``benchmark/families/<family>.py program``), donated, with the
optimizer the traffic file names, on one chip or over the mesh it
names.  Parameters, optimizer state and a cycle of batches are made on
the device from ``--seed``, each in one jitted call, and committed (a
step whose inputs change from uncommitted to committed between two
calls compiles twice: PERF.md, PR 22).

Timing (benchmark/estimators.py whole_step_rate): at most two steps are
in flight -- dispatch i+1, then wait for step i's loss -- so the device
never waits for the host; ``t_i`` is the host time at which loss i
became ready, ``t_0`` belongs to the last warm-up step; dispatching
stops once ``t_i - t_0 >= --seconds``, the step in flight is drained and
``t_n`` is its own fence.
"""

from __future__ import annotations

import time
import types
from typing import Any, Dict, List

from benchmark import correct, estimators
from benchmark.harness import (Ctx, Profiler, memory_peak_bytes,
                               program_overrides, say, span)


def _optimizer(spec: Dict[str, Any]):
    import optax

    if spec["name"] != "adamw":
        raise SystemExit(f"benchmark: unknown optimizer {spec['name']!r}")
    return optax.adamw(float(spec["learning_rate"]),
                       weight_decay=float(spec["weight_decay"]))


def state_shardings(tx, params, p_shard, replicated):
    """Shardings for ``tx.init(params)``: a moment lies where its
    parameter does (its path ends in the parameter's path), anything
    else (the step counter) is replicated.  Left to itself a jitted
    ``tx.init`` replicates the moments -- zeros do not depend on their
    argument -- which for the XL is 12 GB a chip."""
    import jax

    if not isinstance(p_shard, dict):
        return p_shard                       # one device: one sharding
    by_path = dict(jax.tree_util.tree_flatten_with_path(p_shard)[0])

    def pick(path, _leaf):
        for n in range(len(path)):
            if path[n:] in by_path:
                return by_path[path[n:]]
        return replicated

    return jax.tree_util.tree_map_with_path(
        pick, jax.eval_shape(tx.init, params))


def run(ctx: Ctx):
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, SingleDeviceSharding

    from ray_tpu._private.compile_cache import CompileWatch
    from ray_tpu.parallel import MeshSpec, make_mesh
    from ray_tpu.parallel.sharding import (logical_to_mesh_axes,
                                           param_shardings)
    from ray_tpu.train.jax_trainer import jax_utils

    traffic, config = ctx.cell.traffic, ctx.cell.config
    t_phase = time.perf_counter()
    split: Dict[str, float] = {"import_s": t_phase - ctx.t_start}
    watch = CompileWatch()
    devices = jax.devices()[:ctx.cell.chips]
    family, reference = ctx.cell.family, ctx.cell.reference
    model = family.program(config, program_overrides(ctx.cell))
    loss_fn, vocab = model.loss, int(family.sizes(config)["vocab_size"])
    B, T = int(traffic["batch"]), int(traffic["seq"])
    cycle = int(traffic["batch_cycle"])
    tx = _optimizer(traffic["optimizer"])
    mesh_axes = dict(traffic.get("mesh") or {})
    mesh = (make_mesh(MeshSpec(**mesh_axes), devices=devices)
            if mesh_axes else None)

    if mesh is not None:
        axes = model.logical_axes()
        p_shard = param_shardings(axes, mesh)
        b_shard = NamedSharding(mesh, logical_to_mesh_axes(("batch",)))
        replicated = NamedSharding(mesh, logical_to_mesh_axes(()))
    else:
        axes = None
        p_shard = b_shard = replicated = SingleDeviceSharding(devices[0])

    def body():
        nonlocal t_phase
        key = jax.random.PRNGKey(ctx.jax_seed)
        k_w, k_b = jax.random.split(key)
        params = jax.jit(model.init, out_shardings=p_shard)(k_w)
        # committed whole, the step counter too
        opt_state = jax.jit(tx.init, out_shardings=state_shardings(
            tx, params, p_shard, replicated))(params)
        batches = jax.jit(
            lambda k: tuple(
                jax.random.randint(kk, (B, T + 1), 0, vocab,
                                   jnp.int32)
                for kk in jax.random.split(k, cycle)),
            out_shardings=b_shard)(k_b)
        batches = [{"tokens": b} for b in batches]
        jax.block_until_ready((params, opt_state, batches))
        now = time.perf_counter()
        split["weights_s"], t_phase = now - t_phase, now

        # correct, part 1: the system's loss on the first rows of the
        # first batch against the plain reference, before any update
        rows = max(2, len(devices))
        few = {"tokens": batches[0]["tokens"][:rows]}
        loss_sys = float(jax.jit(loss_fn)(params, few))
        loss_ref = float(reference.loss(params, few["tokens"],
                                        vocab_size=vocab,
                                        **ctx.cell.reference_kwargs))
        now = time.perf_counter()
        split["reference_s"], t_phase = now - t_phase, now

        step = jax_utils.build_train_step(
            loss_fn, tx, mesh=mesh, logical_axes=axes,
            telemetry_name="benchmark")
        compiled = step.lower(params, opt_state, batches[0]).compile()
        program_peak = int(
            compiled.memory_analysis().peak_memory_in_bytes)
        n_mosaic = compiled.as_text().count(
            'custom_call_target="tpu_custom_call"')
        losses: List[Any] = []
        i = 0
        for _ in range(int(traffic["warmup_steps"])):
            params, opt_state, loss = step(params, opt_state,
                                           batches[i % cycle])
            loss.block_until_ready()
            losses.append(loss)
            i += 1
        now = time.perf_counter()
        split["compile_and_warmup_s"], t_phase = now - t_phase, now
        say("setup", program_peak_bytes=program_peak,
            mosaic_kernels=n_mosaic, compiles=watch.compiles,
            cache_hits=watch.hits, cache_writes=watch.writes,
            mesh=mesh_axes or None, batch=B, seq=T)

        def dispatch():
            nonlocal params, opt_state, i
            with span("bench.dispatch"):
                params, opt_state, loss = step(params, opt_state,
                                               batches[i % cycle])
            i += 1
            losses.append(loss)
            return loss

        def fence(loss) -> float:
            with span("bench.fence"):
                loss.block_until_ready()
            return time.perf_counter()

        def pipelined(stop) -> List[float]:
            """Fences of a two-deep pipelined run that ends when
            `stop(fences)` says so; fences[0] is the lead-in step's."""
            prev = dispatch()
            nxt = dispatch()
            fences = [fence(prev)]
            prev = nxt
            while True:
                nxt = dispatch()
                fences.append(fence(prev))
                prev = nxt
                if stop(fences):
                    break
            fences.append(fence(prev))
            return fences

        trace = None
        if ctx.trace:
            # a short traced run of its own, before the measured one;
            # whole steps, so the device extent is the window
            prof = Profiler(ctx)
            n_trace = int(traffic["trace_steps"])
            prof.start()
            pipelined(lambda f: len(f) >= n_trace - 1)
            prof.stop()
            trace = prof.reduce()

        compiles_before = watch.compiles
        setup_s = time.perf_counter() - ctx.t_start
        fences = pipelined(
            lambda f: estimators.should_stop(f, ctx.seconds))
        compiles_in_window = watch.compiles - compiles_before
        losses_f = [float(x) for x in np.asarray(
            jax.device_get(losses), np.float32)]
        return types.SimpleNamespace(
            setup_s=setup_s, fences=fences,
            compiles_in_window=compiles_in_window, losses=losses_f,
            loss_sys=loss_sys, loss_ref=loss_ref, trace=trace,
            program_peak=program_peak)

    if mesh is not None:
        with jax.set_mesh(mesh):
            r = body()
    else:
        r = body()

    summary = estimators.step_time_summary(r.fences)
    say("steps", **summary)
    say("setup_split", **{k: round(v, 3) for k, v in split.items()},
        setup_s=round(r.setup_s, 3))
    check = correct.check_train(r.loss_sys, r.loss_ref, r.losses)
    say("correct", **check)
    n_steps = len(r.fences) - 1
    return types.SimpleNamespace(
        ctx=ctx, setup_s=r.setup_s, correct=check["ok"],
        attempted=n_steps, failed=0 if check["all_finite"] else n_steps,
        fences=r.fences, tokens_per_step=B * T, chips=len(devices),
        compiles_in_window=r.compiles_in_window, trace=r.trace,
        shapes={"batch": B, "seq": T, **family.attention_shape(config)},
        flops_per_token=family.train_flops_per_token(config, T),
        memory_peak_bytes=memory_peak_bytes(devices, r.program_peak))
