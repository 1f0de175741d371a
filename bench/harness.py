"""One run of one benchmark cell: set-up, the timed window, the check.

Set-up builds the step with the program's own builders
(``repro.launch.steps.make_btard_scan_train_step`` over device-resident
public-seed data), makes the weights on the device from the seed, and drives
the step through its first two chunks: that warms up the one chunk shape the
window uses, and gives the readings the reference is compared with. The
window then runs whole chunks, each with the host work ``launch/train.py``
does at a chunk boundary (``HostMembership`` events, probes and bans,
``butterfly.checksum_offender_peers``, the audit offenders), until the
seconds have passed. Once the window has closed and the program's state is
freed, the reference follows the same first steps.
"""
from __future__ import annotations

import dataclasses
import gc
import shutil
import sys
import tempfile
import time

import numpy as np

from bench import catalog, compare, flops, reference, trace, weights

CHECK_CHUNKS = 2  # set-up chunks, whose steps the reference follows
COMPILE_EVENTS = ("/jax/core/compile/backend_compile_duration",
                  "/jax/core/compile/jaxpr_to_mlir_module_duration")
PROTOCOL_SEED = (7919, 13)  # launch/train.py's per-step protocol seed map


class NoChip(RuntimeError):
    """The devices JAX finds are not the chips the cell asks for."""


def require_chips(n: int):
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < n:
        raise NoChip(f"the cell needs {n} TPU chip(s); JAX finds "
                     f"{len(devs)} {devs[0].platform} device(s)")


def base_step(seed: int) -> int:
    """The first step of a run: the seed picks where in the public-seed token
    stream the run's data starts, so every seed runs the same program."""
    return int(seed) % (1 << 30)


def _peak_bytes(stats: dict | None):
    """A chip's peak HBM: the TPU runtime counts the buffers in use apart
    from what it holds reserved for the programs' temporaries."""
    if not stats or "peak_bytes_in_use" not in stats:
        return None
    return stats["peak_bytes_in_use"] + stats.get("peak_bytes_reserved", 0)


def device_info(devices) -> dict:
    import jax

    d = jax.devices()[0]
    peak = [_peak_bytes(x.memory_stats()) for x in devices]
    peak = [p for p in peak if p is not None]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(jax.devices()),
            "memory_peak_bytes": max(peak) if peak else None}


def _check_config(cfg, numbers: dict):
    got = dataclasses.asdict(cfg)
    got["pattern"] = [list(s.values()) for s in got["pattern"]]
    bad = {k: (got.get(k), v) for k, v in numbers.items() if got.get(k) != v}
    if bad:
        raise ValueError(f"the program's configuration differs from the "
                         f"configuration file: {bad}")


def _audit_offenders(verif, tol=1e-5):
    """Peers whose validator audit deviated (launch/train.py's rule)."""
    bad = set()
    for k in ("audit_grad_mismatch", "audit_agg_mismatch"):
        if k in verif:
            a = np.asarray(verif[k], np.float64)
            if a.ndim > 1:
                a = a.max(0)
            bad |= {int(i) for i in np.nonzero(a > tol)[0]}
    return bad


class Program:
    """The system under test at a cell's sizes, and its chunk loop."""

    def __init__(self, config: dict, traffic: dict, mesh=None):
        """``mesh``: the devices to build for (default: this machine's, as
        ``launch/train.py`` builds them)."""
        import jax
        import jax.numpy as jnp

        from repro.configs import InputShape, get_config
        from repro.core.aggregators import AggregatorSpec
        from repro.data import TokenPipeline
        from repro.launch.mesh import make_mesh
        from repro.launch.steps import make_btard_scan_train_step
        from repro.models import Model
        from repro.optim import sgd
        from repro.sharding import set_mesh

        cfg = dataclasses.replace(get_config(config["arch"]),
                                  **config["overrides"])
        _check_config(cfg, config["model"])
        self.model = Model(cfg)
        tmpl = weights.template_of(self.model.abstract_params())
        ref_tmpl = {p: (tuple(s), d) for p, (s, d) in
                    reference.template(config["model"]).items()}
        if tmpl != ref_tmpl:
            raise ValueError("the program's parameters differ from the "
                             "reference's template: "
                             f"{sorted(set(tmpl.items()) ^ set(ref_tmpl.items()))}")
        tr = traffic
        self.n, self.b, self.seq = tr["mesh"][0], tr["per_peer_batch"], tr["seq"]
        self.scan = tr["scan_steps"]
        self.attack = tr["attack"]
        self.mu = tr["optimizer"]["momentum"]
        self.mesh = mesh or make_mesh(tr["mesh"], ("data", "model"))
        set_mesh(self.mesh)
        opt = tr["optimizer"]
        if opt["kind"] != "sgd":
            raise ValueError(f"optimizer {opt['kind']!r} is not built here")
        self.opt = sgd(opt["lr"], momentum=opt["momentum"],
                       nesterov=opt["nesterov"])
        data = tr["data"]
        pipe = TokenPipeline(cfg.vocab_size, self.seq, self.n * self.b,
                             a=data["a"], c=data["c"], noise=data["noise"],
                             global_seed=data["global_seed"])
        agg = tr["aggregator"]
        self.step, self.abstract = make_btard_scan_train_step(
            self.model, self.opt, self.mesh,
            InputShape("bench", self.seq, self.n * self.b, "train"),
            n_scan_steps=self.scan, tau=agg["tau"], clip_iters=agg["n_iters"],
            attack=self.attack, use_pallas=tr["use_pallas"],
            aggregator=AggregatorSpec.parse(agg["spec"]), pipeline=pipe,
        )
        self.gen = weights.make_generator(tmpl)
        self.init_opt = jax.jit(self.opt.init)
        self.zeros = jax.jit(lambda t: jax.tree.map(jnp.zeros_like, t))
        self.byz = jnp.zeros((self.n,), jnp.float32)
        self.tokens_per_step = self.n * self.b * self.seq
        mu = self.mu
        self.grad0 = jax.jit(lambda m, v: {
            weights.path_of(p): (a - b.astype(jnp.float32)) / mu
            for (p, a), b in zip(jax.tree_util.tree_flatten_with_path(m)[0],
                                 jax.tree.leaves(v))})
        self.change_norms = jax.jit(lambda a, z: {
            weights.path_of(p): jnp.linalg.norm(
                (x.astype(jnp.float32) - y.astype(jnp.float32)).ravel())
            for (p, x), y in zip(jax.tree_util.tree_flatten_with_path(a)[0],
                                 jax.tree.leaves(z))})

    def start(self, seed: int):
        """Fresh weights, optimizer state and membership for ``seed``."""
        from repro.core.sybil import HostMembership

        self.key_data = weights.seed_key_data(seed)
        params = weights.to_tree(self.gen(self.key_data),
                                 self.model.abstract_params())
        self.state = [params, self.init_opt(params), self.zeros(params)]
        self.mem = HostMembership(self.n)
        self.next_step = base_step(seed)
        self.accused = 0  # peers a violated digest checksum implicated

    def chunk(self):
        """One dispatch of ``scan`` steps and its chunk-boundary host work;
        returns the chunk's losses."""
        import jax
        import jax.numpy as jnp

        from repro.core import butterfly as bf

        span = jax.profiler.TraceAnnotation
        idxs = list(range(self.next_step, self.next_step + self.scan))
        self.next_step += self.scan
        with span("bench.boundary"):
            for s in idxs:
                self.mem.apply_events(s)
            w = jnp.asarray(self.mem.weights())
            steps = jnp.asarray(idxs, jnp.int32)
            seeds = jnp.asarray(
                [(s * PROTOCOL_SEED[0] + PROTOCOL_SEED[1]) % (1 << 31)
                 for s in idxs], jnp.int32)
        params, opt_state, v = self.state
        with span("bench.dispatch"):
            params, opt_state, metrics, verif, v = self.step(
                params, opt_state, steps, seeds, self.byz, w, v)
        self.state = [params, opt_state, v]
        with span("bench.fetch"):
            probes = np.asarray(verif["probe_mismatch"], np.float64)
            losses = np.asarray(metrics["loss"], np.float64)
        with span("bench.boundary"):
            if probes.ndim == 1:
                probes = probes[None]
            for i, s in enumerate(idxs):
                self.mem.observe_probe(probes[i], s)
            sums = np.asarray(verif["checksum"], np.float32).reshape(-1, self.n)
            self.accused += sum(len(bf.checksum_offender_peers(c)) for c in sums)
            bad = bf.checksum_offender_peers(sums[-1])
            if self.attack == "none":
                bad = []
            self.mem.ban_slots({int(b) for b in bad} | _audit_offenders(verif),
                               idxs[-1])
        return losses

    def first_steps(self) -> dict:
        """Drive the set-up chunks; the readings the reference is held to
        (the first gradient whole, on the host, the rest as norms)."""
        import jax

        losses = list(self.chunk())
        params, opt_state, v = self.state
        grad0_vec = jax.device_get(self.grad0(opt_state["m"], v))
        for _ in range(CHECK_CHUNKS - 1):
            losses += list(self.chunk())
        start = weights.to_tree(self.gen(self.key_data),
                                self.model.abstract_params())
        update = {k: float(x) for k, x in
                  self.change_norms(self.state[0], start).items()}
        return {"loss": losses, "update": update, "grad0_vec": grad0_vec,
                "grad0": {k: float(np.linalg.norm(x.astype(np.float64)))
                          for k, x in grad0_vec.items()}}

    def free(self):
        self.state = None


class _Compiles:
    """Counts the compilations between entering and leaving the block."""

    def __enter__(self):
        import jax

        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)
        return self

    def _on(self, event, *_a, **_k):
        if event in COMPILE_EVENTS:
            self.n += 1

    def __exit__(self, *exc):
        import jax

        jax.monitoring.unregister_event_duration_listener(self._on)


def run_cell(name: str, seed: int, seconds: float, traced: bool, *,
             root=catalog.ROOT, bench=catalog.BENCH, require_tpu=True,
             t0=None, log=sys.stderr):
    """Run one cell; returns the result dict the benchmark prints (the
    numbers compared under ``checks``, last)."""
    t0 = time.time() if t0 is None else t0
    bm = catalog.load_benchmark(root)
    cell = catalog.workload(bm, name)
    config = catalog.config(bm, cell["config"], root)
    traffic = catalog.traffic(cell["traffic"], bench)
    limits = catalog.limits(name, bench)
    if require_tpu:
        require_chips(cell["chips"])

    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    kind = jax.devices()[0].device_kind
    peaks = flops.peaks(kind) if require_tpu else None

    prog = Program(config, traffic)
    prog.start(seed)
    readings = prog.first_steps()
    setup_s = time.time() - t0

    gc.collect()  # the set-up's garbage is collected in the set-up
    gc.freeze()
    tmp = tempfile.mkdtemp(prefix="bench-trace-") if traced else None
    if traced:
        jax.profiler.start_trace(tmp)
    chunks, failed = 0, 0
    with _Compiles() as compiles:
        t_w0 = time.perf_counter()
        while True:
            losses = prog.chunk()
            chunks += 1
            failed += int(np.sum(~np.isfinite(losses)))
            if time.perf_counter() - t_w0 >= seconds:
                break
        jax.block_until_ready(prog.state)
        window_s = time.perf_counter() - t_w0
    gc.unfreeze()
    if traced:
        jax.profiler.stop_trace()
    in_window = compiles.n
    steps = chunks * prog.scan
    tokens_per_s = steps * prog.tokens_per_step / window_s
    device = device_info(prog.mesh.devices.flat)
    bans = len(prog.mem.banned_slots())
    accused = prog.accused
    prog.free()
    del prog
    print(f"window: {chunks} chunks, {steps} steps in {window_s:.3f} s; "
          f"compilations in the window: {in_window}", file=log)

    ref = reference.follow(config, traffic, seed, base_step(seed),
                           n_steps=CHECK_CHUNKS * traffic["scan_steps"])
    nums = compare.numbers(readings, ref)
    correct, checks = compare.judge(nums, limits, bans, accused)

    result = {"correct": correct, "attempted": steps, "failed": failed}
    if traced:
        red = trace.reduce(trace.load(tmp))
        shutil.rmtree(tmp, ignore_errors=True)
        device.update(busy_s=red["busy_s"], window_s=window_s)
        ctx = {"trace": red, "steps": steps, "window_s": window_s,
               "tokens_per_s": tokens_per_s, "chips": cell["chips"],
               "peaks": peaks, "config": config, "traffic": traffic,
               "flops_per_token": flops.flops_per_token(
                   config["model"], traffic["seq"])}
        metrics = {}
        for m in catalog.metrics_of(bm, name, "per_layer"):
            v = catalog.reader(m["name"], bench)(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        result["metrics"] = metrics
        result["breakdown"] = {"device_ops": red["device_ops"],
                               "idle_gaps": red["idle_gaps"]}
    else:
        values = {"setup_s": setup_s, "tokens_per_s": tokens_per_s,
                  "peak_hbm_gib": (device["memory_peak_bytes"] or 0) / 2**30}
        result["metrics"] = {
            m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
            for m in catalog.metrics_of(bm, name, "end_to_end")}
    result["device"] = device
    result["checks"] = checks
    for k, (v, detail) in nums.items():
        if k not in checks:
            print(f"note {k} {v} {detail}", file=log)
    for k, c in checks.items():
        detail = nums[k][1] if k in nums else compare.COUNTS[k]
        print(f"check {k} {c['value']!r} limit {c['limit']!r} ({detail})",
              file=log)
    return result
