"""Train/evaluate/infer driver — the reference's `BaseEstimator`
(euler_estimator/python/base_estimator.py:28-188) rebuilt JAX-style.

The model contract matches the reference (mp_utils/base.py:24-95): a flax
module whose __call__ returns (embedding, loss, metric_name, metric). Batches
come from host-side generator functions (graph sampling + dataflow queries),
get device_put, and run through one jitted update step. Checkpoints are
retained step directories (training/checkpoint.py); inference writes
embedding_{worker}.npy / ids_{worker}.npy like base_estimator.py:157-179.
"""

from __future__ import annotations

import contextlib
import dataclasses
import functools
import os
import threading
import time
import weakref
from typing import Callable, Iterable, Iterator

import jax
import jax.numpy as jnp
import numpy as np
import optax

from euler_tpu.utils import trace


@dataclasses.dataclass
class EstimatorConfig:
    model_dir: str = "/tmp/euler_tpu_model"
    batch_size: int = 32
    total_steps: int = 100
    learning_rate: float = 0.01
    optimizer: str = "adam"  # adam | adagrad | sgd | momentum
    momentum: float = 0.9
    log_steps: int = 20
    checkpoint_steps: int = 0  # 0 = only at end
    # retained atomic checkpoints (euler_tpu/training/checkpoint.py):
    # save() commits step-numbered ckpt_<step>/ dirs and keeps this many
    # complete ones — a crash mid-save can never lose the previous good
    # state. restore() picks the newest COMPLETE one.
    keep_checkpoints: int = 3
    seed: int = 0
    # profiling (BaseEstimator(profiling=True) parity, base_estimator.py:
    # 130-133): when set, a profiler trace of `profile_steps` steps is
    # written there once, starting at `profile_start_step`; it shows the
    # `euler.*` scopes and spans (OPERATIONS.md, "Reading a training trace")
    profile_dir: str = ""
    profile_start_step: int = 10
    profile_steps: int = 5
    # steps per XLA dispatch: >1 runs a lax.scan of K optimizer steps over
    # batches stacked on a leading K axis (batch_fn must return them that
    # way, e.g. via `stack_batches`). Amortizes host→device dispatch latency
    # — the TPU analog of the reference keeping its query pipeline async
    # (query_proxy.cc:205-256) so the trainer never stalls per step.
    steps_per_call: int = 1


# The ONE table both the optimizer factory and its cache key derive from:
# per optimizer name, the EstimatorConfig fields the built transformation
# reads. make_optimizer consumes fields only through this table, so a new
# knob that is not declared here raises at construction instead of
# silently sharing one cached update program between differing configs.
_OPTIMIZER_CFG_FIELDS: dict[str, tuple[str, ...]] = {
    "adam": ("learning_rate",),
    "adagrad": ("learning_rate",),
    "sgd": ("learning_rate",),
    "momentum": ("learning_rate", "momentum"),
}

_OPTIMIZER_FACTORIES = {
    "adam": lambda a: optax.adam(a["learning_rate"]),
    "adagrad": lambda a: optax.adagrad(a["learning_rate"]),
    "sgd": lambda a: optax.sgd(a["learning_rate"]),
    "momentum": lambda a: optax.sgd(
        a["learning_rate"], momentum=a["momentum"]
    ),
}


def make_optimizer(cfg: EstimatorConfig) -> optax.GradientTransformation:
    """Optimizer factory (tf_euler/python/utils/optimizers.py parity).
    Reads cfg ONLY through _OPTIMIZER_CFG_FIELDS, which also drives
    _optimizer_key — the factory and the jit-cache key cannot drift."""
    if cfg.optimizer not in _OPTIMIZER_CFG_FIELDS:
        raise ValueError(f"unknown optimizer {cfg.optimizer!r}")
    args = {
        f: getattr(cfg, f) for f in _OPTIMIZER_CFG_FIELDS[cfg.optimizer]
    }
    return _OPTIMIZER_FACTORIES[cfg.optimizer](args)


def _optimizer_key(cfg: EstimatorConfig) -> tuple:
    """Shared-jit cache key: derived mechanically from the cfg fields
    make_optimizer consumes for THIS optimizer, so a field the update
    program never reads (e.g. momentum under adam) cannot force a
    spurious retrace, and a consumed field can never be missed."""
    return (cfg.optimizer,) + tuple(
        getattr(cfg, f) for f in _OPTIMIZER_CFG_FIELDS[cfg.optimizer]
    )



# Jitted programs are shared ACROSS Estimator instances: tracing +
# lowering an identical train step costs seconds per instance on a host
# core even when the persistent compile cache spares the XLA compile
# (re-instantiation patterns: determinism reruns, warm-started TransX
# chains, hyperparameter sweeps, serving runtimes). The cache dict is
# keyed BY the user's flow (else feature-cache) object in a module-level
# WeakKeyDictionary — not injected as an attribute onto the user's object
# (ADVICE r5: attribute injection broke copy.deepcopy/pickle of flows
# after training) and not a strong global — so the cached closures never
# outlive the objects whose device buffers they pin: drop the flow/cache
# and the weak entry (and every program traced against it) is freed with
# it. Entries are keyed by everything else the traced program reads: the
# flax model (structural digest), the cfg fields make_optimizer consumes,
# rng collections, the mesh, and the identity of the non-root partner
# object (its id cannot be recycled while the entry exists, because the
# closure holds it). Estimators with neither a device flow nor a feature
# cache have no root to pin the lifetime to and simply keep the
# pre-existing per-instance behavior. Get-or-build runs under
# _JIT_CACHE_LOCK so concurrent serving threads can't race a build.
# EULER_TPU_STEP_CACHE=0 disables all sharing.


def _structural_key(v):
    """Collision-safe, hashable digest of a model's configuration.

    repr(model) alone is NOT safe as a cache key: numpy summarizes large
    arrays ("[0. 0. ... 0.]"), so two models differing only in a big
    constant field repr identically and would silently share one traced
    program — a wrong-result bug, not a perf bug. This walks the
    dataclass fields structurally instead: scalars/strings by value,
    containers recursively, arrays by dtype/shape/content digest, nested
    modules by their own fields. A field of a type this function does not
    understand degrades to identity (`id`) — that model never SHARES a
    cached program (costing a retrace), which is the correct default for
    unknown state.
    """
    import hashlib

    if v is None or isinstance(v, (bool, int, float, str, bytes)):
        return v
    if isinstance(v, (list, tuple)):
        return ("seq", tuple(_structural_key(x) for x in v))
    if isinstance(v, dict):
        return (
            "map",
            tuple(
                (str(k), _structural_key(v[k]))
                for k in sorted(v, key=str)
            ),
        )
    if isinstance(v, type):
        return ("type", v.__module__, v.__qualname__)
    if isinstance(v, np.dtype):
        return ("dtype", str(v))
    if hasattr(v, "shape") and hasattr(v, "dtype"):  # numpy / jax array
        arr = np.asarray(v)
        return (
            "array", str(arr.dtype), tuple(arr.shape),
            hashlib.sha1(np.ascontiguousarray(arr).tobytes()).hexdigest(),
        )
    if dataclasses.is_dataclass(v) and not isinstance(v, type):
        # nested flax submodule / config dataclass; parent would recurse
        # back up the module tree and name is identity-free metadata
        return (
            "dc", type(v).__module__, type(v).__qualname__,
            tuple(
                (f.name, _structural_key(getattr(v, f.name)))
                for f in dataclasses.fields(v)
                if f.name not in ("parent", "name")
            ),
        )
    if callable(v) and hasattr(v, "__qualname__"):
        # module-level functions (activations etc.) key by location;
        # closures/lambdas share a qualname but can differ in behavior,
        # so they fall through to identity below
        if "<locals>" not in v.__qualname__ and "<lambda>" not in (
            v.__qualname__
        ):
            return ("fn", getattr(v, "__module__", ""), v.__qualname__)
    return ("id", id(v))


# per-root entry bound: each entry's closure can pin a partner object's
# device buffers (e.g. a non-root DeviceFeatureCache's feature table), so
# a sweep that misses every lookup (varying lr / fresh caches against one
# shared flow) must not accumulate pins without bound — FIFO-evicting at
# a small cap frees the evicted closure and everything only it pinned
_JIT_CACHE_MAX = 8


_JIT_CACHES: "weakref.WeakKeyDictionary" = weakref.WeakKeyDictionary()
# one process-wide reentrant lock over every get-or-build: build work under
# it is cheap (jax.jit only wraps; tracing happens at first call), and a
# single lock cannot deadlock against itself on the nested
# _ensure_steps → _jit_cache path
_JIT_CACHE_LOCK = threading.RLock()


def _jit_cache(root) -> dict | None:
    """The per-object jit-program cache rooted on `root`, or None when
    sharing is off / there is no root."""
    if root is None or os.environ.get("EULER_TPU_STEP_CACHE", "1") == "0":
        return None
    with _JIT_CACHE_LOCK:
        cache = _JIT_CACHES.get(root)
        if cache is None:
            try:
                _JIT_CACHES[root] = cache = {}
            except TypeError:  # not weak-referenceable: no sharing
                return None
    return cache


def _jit_cache_put(cache: dict, key, value):
    # "probe" is exempt from eviction: it is the first insertion and the
    # one entry every Estimator on the flow re-uses, so FIFO would recycle
    # exactly the wrong entry in an all-miss sweep
    evictable = [k for k in cache if k != "probe"]
    while len(evictable) >= _JIT_CACHE_MAX:
        cache.pop(evictable.pop(0))
    cache[key] = value


def _live_tables(mesh, device_flow, feature_cache) -> dict:
    """The table argument of a program: the owners' staged arrays as they
    are NOW, so a `refresh_rows` / `commit()` since the last dispatch is
    what the next one reads. Under a mesh the tables are replicated in
    place the first time they pass here and found so afterwards."""
    tables = {}
    for name, owner in (("flow", device_flow), ("features", feature_cache)):
        if owner is not None:
            if mesh is not None:
                owner.replicate(mesh)
            tables[name] = owner.tables()
    return tables


def _bound(owner, tables: dict, name: str):
    """The owner as the traced code reads it: a view whose arrays are
    its part of the program's `tables` argument."""
    return None if owner is None else owner.bind(tables[name])


def _bind_tables(device_flow, feature_cache, tables: dict) -> tuple:
    return (
        _bound(device_flow, tables, "flow"),
        _bound(feature_cache, tables, "features"),
    )


def _flow_probe(flow):
    """Jitted `(tables, key) -> flow.sample(key)` for the init-shape
    probe, memoized on the flow (a fresh jax.jit wrapper would re-trace
    for every Estimator sharing the flow)."""

    def sample(tables, key):
        return flow.bind(tables).sample(key)

    cache = _jit_cache(flow)
    if cache is None:
        return jax.jit(sample)
    with _JIT_CACHE_LOCK:
        if "probe" not in cache:
            _jit_cache_put(cache, "probe", jax.jit(sample))
        return cache["probe"]


def _hydrate_batch(feature_cache, batch: tuple) -> tuple:
    from euler_tpu.dataflow.base import MiniBatch, hydrate_blocks

    with trace.scope("hydrate"):
        batch = tuple(
            hydrate_blocks(b) if isinstance(b, MiniBatch) else b for b in batch
        )
        return (
            feature_cache.hydrate_args(batch)
            if feature_cache is not None
            else batch
        )


def _apply_update(model, tx, feature_cache, params, opt_state, step_rngs, batch):
    """One traced optimizer step: hydrate → loss/grad → update."""
    batch = _hydrate_batch(feature_cache, batch)

    def loss_fn(p):
        _, loss, _, metric = model.apply(p, *batch, rngs=step_rngs)
        return loss, metric

    # a function of the lowered module (XLA inlines it) as well as a scope:
    # the persistent compile cache keys on the module WITHOUT its metadata,
    # so a step that differed from an older one by scope names alone would
    # be handed the older executable, and its op names, on a hit
    @jax.jit
    def optimizer(grads, opt_state, params):
        updates, opt_state = tx.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state

    (loss, metric), grads = jax.value_and_grad(loss_fn, has_aux=True)(params)
    with trace.scope("optimizer"):
        params, opt_state = optimizer(grads, opt_state, params)
    return params, opt_state, loss, metric


def _step_args(device_flow, xs):
    """Per-step scan/step input → model args. Host flows ship the batch
    itself; device flows ship a PRNG key and sample on device. A flow
    returning a tuple supplies multiple model args (e.g. the unsupervised
    (src, pos, negs) triple)."""
    if device_flow is not None:
        with trace.scope("sample"):
            out = device_flow.sample(xs[0])
        return out if isinstance(out, tuple) else (out,)
    return xs


def _build_train_steps(model, tx, device_flow, feature_cache):
    """The two jitted update programs. They close over the model, the
    optimizer and the flow / feature-cache OBJECTS, for what those fix at
    trace time (fanouts, batch size, layout, quantization) — never over
    an instance's params, so they are shareable across Estimators via
    _jit_cache. The objects' device tables are not read from the closure:
    they are the `tables` argument (`_live_tables`, not donated), bound
    to views of the owners inside the trace."""

    # donate params+opt_state: without it the update keeps both old and
    # new buffers alive across the step — 2x the HBM for model state
    # (the big cost for sharded embedding tables)
    @functools.partial(jax.jit, donate_argnums=(0, 1))
    def train_step(params, opt_state, tables, rngs, *batch):
        flow, cache = _bind_tables(device_flow, feature_cache, tables)
        return _apply_update(
            model, tx, cache,
            params, opt_state, rngs, _step_args(flow, batch),
        )

    @functools.partial(jax.jit, donate_argnums=(0, 1))
    def multi_step(params, opt_state, tables, rngs, *stacked_batch):
        # bound outside the body: the tables are loop invariants of the
        # scan, not scanned inputs
        flow, cache = _bind_tables(device_flow, feature_cache, tables)

        def body(carry, xs):
            params, opt_state = carry
            step_rngs, batch = xs
            params, opt_state, loss, metric = _apply_update(
                model, tx, cache,
                params, opt_state, step_rngs, _step_args(flow, batch),
            )
            return (params, opt_state), (loss, metric)

        (params, opt_state), (losses, metrics) = jax.lax.scan(
            body, (params, opt_state), (rngs, stacked_batch)
        )
        return params, opt_state, losses, metrics[-1]

    return train_step, multi_step


_NO_SPAN = contextlib.nullcontext()


@contextlib.contextmanager
def _first_call(program: str, tables: dict):
    """The set-up span `step.first_call` around the first execution of a
    step program (the caller waits for the result inside it): tracing,
    lowering, cache fetch or compile are its child spans, and what
    remains is the first run. Traces nest (an inner `jax.jit` is traced,
    lowered and compiled inside the outer trace), so each of `.trace`,
    `.lower` and `.compile` is recorded as the stretches that are its
    own (`trace.self_stretches`): the three cover disjoint time, and
    `.cache_fetch` lies inside `.compile`. `table_arg_bytes` is what
    went in as the `tables` argument rather than as constants of the
    executable; the other args are the tallies that moved while the
    program was traced (`trace.count`), each named and described at the
    site that counts it."""
    table_arg_bytes = sum(
        leaf.nbytes for leaf in jax.tree_util.tree_leaves(tables)
    )
    with trace.span(
        "step.first_call", program=program, table_arg_bytes=table_arg_bytes
    ) as call, trace.compiles() as events:
        before = trace.counts()
        yield
        after = trace.counts()
        call.args.update(
            (k, n - before.get(k, 0)) for k, n in after.items() if n != before.get(k, 0)
        )
        parts = trace.self_stretches(events, ("trace", "lower", "compile"))
        parts.update(trace.self_stretches(events, ("cache_fetch",)))
        for kind, stretches in parts.items():
            for lo, hi in stretches:
                call.child(f"step.first_call.{kind}", lo, hi, program=program)


class _ProfileWindow:
    """`cfg.profile_dir`: one profiler trace per Estimator, of
    `profile_steps` steps (at least `min_steps`) from `profile_start_step`
    on. Every dispatch of the traced stretch runs inside a
    `StepTraceAnnotation("euler.step")`, so the trace has steps; the
    `euler.*` scopes and spans show in it with no further code."""

    def __init__(self, est: "Estimator", min_steps: int = 1):
        self._est = est
        self._min_steps = min_steps
        self._live = False
        self._stop_at = 0

    def step(self):
        """Before a dispatch: starts the trace when it is due; returns
        the context the dispatch runs in."""
        est, cfg = self._est, self._est.cfg
        if (
            cfg.profile_dir
            and not est._profiled
            and est.step >= cfg.profile_start_step
        ):
            jax.profiler.start_trace(cfg.profile_dir)
            est._profiled = self._live = True
            self._stop_at = est.step + max(cfg.profile_steps, self._min_steps)
        if not self._live:
            return _NO_SPAN
        return jax.profiler.StepTraceAnnotation("euler.step", step_num=est.step)

    def stop_if_done(self, result) -> None:
        if self._live and self._est.step >= self._stop_at:
            self.stop(result)

    def stop(self, result) -> None:
        """Waits for `result`, so the trace holds the device's part of the
        last step, and writes the trace out."""
        if self._live:
            self._live = False
            try:
                jax.block_until_ready(result)
            finally:
                jax.profiler.stop_trace()


class Estimator:
    """Drives a (emb, loss, metric_name, metric) flax model.

    batch_fn() must return a *tuple* of pytrees passed as model args —
    (MiniBatch,) for supervised heads, (src, pos, negs) for unsupervised.
    """

    def __init__(
        self,
        model,
        batch_fn: Callable[[], tuple],
        cfg: EstimatorConfig | None = None,
        mesh=None,
        feature_cache=None,
        init_params=None,
    ):
        """init_params: warm-start parameter pytree (already unboxed) —
        overrides model.init at first train/eval. Staged recipes use this:
        e.g. TransR/TransD initialized from a trained TransE's tables
        (the published TransR training protocol)."""
        self.model = model
        self.batch_fn = batch_fn
        # a DeviceSageFlow (is_device_flow) generates batches ON the
        # device inside the jitted step from per-step PRNG keys — the
        # drivers then ship keys instead of batches (zero wire bytes)
        self._device_flow = (
            batch_fn if getattr(batch_fn, "is_device_flow", False) else None
        )
        self.cfg = cfg or EstimatorConfig()
        self.mesh = mesh  # jax.sharding.Mesh → data-parallel + sharded tables
        # DeviceFeatureCache: batches arrive as int32 feature rows and are
        # hydrated to dense features on device, inside the jitted step
        self.feature_cache = feature_cache
        self.params = None
        self._init_params = init_params
        self.opt_state = None
        self.step = 0
        # losses fetched by the most recent train() — populated even
        # when the loop raises (try/finally drain), so a crash surfaces
        # the trajectory observed so far
        self.last_losses: list = []
        self.tx = make_optimizer(self.cfg)
        # models may declare extra rng collections (e.g. VGAE's "reparam")
        self._rng_names = tuple(getattr(model, "rng_collections", ()))
        self._base_key = jax.random.PRNGKey((cfg or EstimatorConfig()).seed + 1)
        # device-flow sampling keys: folded per GLOBAL step, so the batch
        # sequence is deterministic and independent of steps_per_call
        self._flow_key = jax.random.PRNGKey(self.cfg.seed + 2)
        if self._device_flow is not None:
            fm = getattr(self._device_flow, "mesh", None)
            if (fm is None) != (mesh is None) or (
                mesh is not None and fm != mesh
            ):
                raise ValueError(
                    "device-flow training needs the Estimator and the flow "
                    "to share one mesh (DeviceSageFlow(..., mesh=mesh)) so "
                    "sampled batches are data-axis sharded; got flow mesh "
                    f"{fm} vs estimator mesh {mesh}"
                )
        self._jit_train = None
        self._jit_train_scan = None
        self._jit_eval = None
        self._jit_embed = None
        self._called: set = set()  # step programs this Estimator has run
        self._profiled = False  # cfg.profile_dir's one trace was taken

    # -- state -----------------------------------------------------------

    def _put(self, batch, stacked: bool = False):
        if self.mesh is None:
            return batch
        from euler_tpu.parallel import shard_batch

        # stacked [K_steps, batch, ...] items shard axis 1 (the real batch
        # axis); the scan axis stays unsharded
        return shard_batch(batch, self.mesh, batch_axis=1 if stacked else 0)

    def _hydrate(self, batch: tuple) -> tuple:
        return _hydrate_batch(self.feature_cache, batch)

    def _tables(self, flow: bool = True) -> dict:
        """This dispatch's `tables` argument; `flow=False` for the eval
        and embed programs, which read the feature cache only."""
        return _live_tables(
            self.mesh, self._device_flow if flow else None, self.feature_cache
        )

    def _ensure_init(self):
        if self.params is not None:
            if self.opt_state is None:
                self.opt_state = self.tx.init(self.params)
            return
        import flax.linen as nn

        if self._init_params is not None and self.mesh is None:
            # COPY the warm-start arrays: the donated train step would
            # otherwise invalidate the caller's buffers on TPU (e.g. a
            # trained TransE whose tables seed TransR via
            # transx_warm_start) — buffer donation is a no-op on CPU, so
            # only real-device runs would hit the corruption
            self.params = jax.tree_util.tree_map(
                lambda x: jnp.array(x, copy=True), self._init_params
            )
            self.opt_state = self.tx.init(self.params)
            return
        if self._device_flow is not None:
            out = _flow_probe(self._device_flow)(
                self._tables()["flow"], self._flow_keys(0, 1)[0]
            )
            batch = out if isinstance(out, tuple) else (out,)
        else:
            batch = self._put(
                self.batch_fn(), stacked=self.cfg.steps_per_call > 1
            )
            if self.cfg.steps_per_call > 1:  # stacked [K,...] → init slice 0
                batch = jax.tree_util.tree_map(lambda x: x[0], batch)
        batch = self._hydrate(batch)
        key = jax.random.PRNGKey(self.cfg.seed)
        keys = jax.random.split(key, 1 + len(self._rng_names))
        rngs = {"params": keys[0]}
        rngs.update(dict(zip(self._rng_names, keys[1:])))
        params = self.model.init(rngs, *batch)
        if self.mesh is not None:
            from euler_tpu.parallel import unbox_and_shard

            params, _ = unbox_and_shard(self.mesh, params)
            if self._init_params is not None:
                # warm-start under a mesh: the cold init above provides
                # the placement template (row-sharded tables etc.); the
                # warm values are device_put onto the same shardings so
                # model parallelism survives the warm start. copy=True is
                # load-bearing: device_put aliases a src that already has
                # the target sharding, and the donated train step would
                # then delete the CALLER's buffers (the donor model's
                # params) on real devices
                params = jax.tree_util.tree_map(
                    lambda tgt, src: jax.device_put(
                        jnp.array(src, copy=True), tgt.sharding
                    ),
                    params,
                    self._init_params,
                )
        else:
            params = nn.meta.unbox(params)
        self.params = params
        self.opt_state = self.tx.init(self.params)

    def _rngs(self, step: int):
        if not self._rng_names:
            return None
        k = jax.random.fold_in(self._base_key, step)
        return dict(zip(self._rng_names, jax.random.split(k, len(self._rng_names))))



    def _model_key(self) -> tuple:
        m = self.model
        return (type(m).__module__, type(m).__qualname__, _structural_key(m))

    def _ensure_steps(self):
        """Bind the jitted step pair, shared via the root object's jit
        cache when possible (see _jit_cache above)."""
        if self._jit_train is not None:
            return
        # root on the flow when there is one, else on the feature cache:
        # the programs hold both objects (for their static configuration;
        # the tables come in as arguments), so an entry lives as long as
        # its root, and the flow outliving the cache is the unusual case
        root = (
            self._device_flow
            if self._device_flow is not None
            else self.feature_cache
        )
        cache = _jit_cache(root)
        if cache is None:
            # same lock as the shared-cache path below: an Estimator shared
            # by serving threads with sharing disabled must still agree on
            # ONE program pair instead of racing build-and-overwrite
            # (build is cheap under the lock — jax.jit only wraps)
            with _JIT_CACHE_LOCK:
                if self._jit_train is None:
                    self._jit_train, self._jit_train_scan = (
                        _build_train_steps(
                            self.model, self.tx, self._device_flow,
                            self.feature_cache,
                        )
                    )
            return
        key = (
            "steps",
            self._model_key(),
            _optimizer_key(self.cfg),
            self._rng_names,
            id(self.feature_cache)
            if self.feature_cache is not None and root is not self.feature_cache
            else None,
            self.mesh,
        )
        # get-or-build under the lock: two serving/training threads racing
        # here must agree on ONE program pair, not each build-and-overwrite
        with _JIT_CACHE_LOCK:
            if key not in cache:
                _jit_cache_put(
                    cache,
                    key,
                    _build_train_steps(
                        self.model, self.tx, self._device_flow,
                        self.feature_cache,
                    ),
                )
            self._jit_train, self._jit_train_scan = cache[key]

    def _train_step(self):
        self._ensure_steps()
        return self._jit_train

    def _train_step_scan(self):
        """K optimizer steps per dispatch via lax.scan over stacked batches
        (host flows) or per-step sampling keys (device flows)."""
        self._ensure_steps()
        return self._jit_train_scan

    def _rngs_stacked(self, step: int, k: int):
        if not self._rng_names:
            return None
        return jax.vmap(lambda s: self._rngs(s))(jnp.arange(step, step + k))

    def _flow_keys(self, step: int, k: int):
        """[k]-stacked device-flow sampling keys for global steps
        step..step+k (fold_in per step: the batch stream is reproducible
        and invariant to how steps are grouped into dispatches)."""
        return jax.vmap(lambda s: jax.random.fold_in(self._flow_key, s))(
            jnp.arange(step, step + k)
        )

    def _next_batch(self, k: int):
        """One dispatch's batch args: K-stacked host batch or K sampling
        keys (device flow)."""
        if self._device_flow is not None:
            if k > 1:
                return (self._flow_keys(self.step, k),)
            return (jax.random.fold_in(self._flow_key, self.step),)
        return self._put(self.batch_fn(), stacked=k > 1)

    # -- drivers (train/evaluate/infer/train_and_evaluate) ---------------

    def train(
        self, total_steps: int | None = None, log: bool = True, save: bool = True
    ):
        steps = total_steps if total_steps is not None else self.cfg.total_steps
        with trace.counted("train", steps=steps):
            self._ensure_init()
            k = max(int(self.cfg.steps_per_call), 1)
            if k > 1:
                return self._train_scan(steps, k, log=log, save=save)
            return self._train_steps(steps, log=log, save=save)

    def _dispatch(self, step_fn, rngs, batch):
        """One call of a step program on the Estimator's state; returns
        its (loss or losses, metric) and the `args` of its
        `train.dispatch` span, which the drain that fetches the metric
        adds it to. The first call of each program is waited for and
        recorded as a set-up span (`_first_call`)."""
        with trace.span("train.dispatch", step=self.step) as span:
            name = step_fn.__name__
            first = name not in self._called
            tables = self._tables()
            with _first_call(name, tables) if first else _NO_SPAN:
                self.params, self.opt_state, loss, metric = step_fn(
                    self.params, self.opt_state, tables, rngs, *batch
                )
                if first:
                    self._called.add(name)
                    jax.block_until_ready(loss)
            if trace.profiling():
                # the model's metric of a profiled step, left on the
                # device: whoever reads the record fetches it
                span.args["metric"] = metric
        return loss, metric, span.args

    def _drain(self, history: list, fetched: list, concat: bool) -> None:
        """Fetches what `history` holds on the device, a (loss or
        losses, metric, its dispatch span's `args`) a dispatch, in one
        join and one copy: the losses into `fetched`, each metric into
        those `args` as the float `model_metric`."""
        losses, metrics, dispatched = zip(*history)
        steps = sum(row.shape[0] for row in losses) if concat else len(losses)
        with trace.counted("train.drain", step=self.step):
            if concat:  # a dispatch's losses are a row: its metric joins as one
                joined = jnp.concatenate([*losses, *(m[None] for m in metrics)])
            else:
                joined = jnp.stack([*losses, *metrics])
            # asked for before the wait, as `np.asarray` alone would: the
            # transfer follows the join on the device with no round trip
            # through the host between them
            joined.copy_to_host_async()
            with trace.span("train.drain.wait"):
                jax.block_until_ready(joined)  # the device, or the runtime
            with trace.span("train.drain.copy"):
                values = np.asarray(joined).tolist()  # what is left of it
        fetched.extend(values[:steps])
        for args, value in zip(dispatched, values[steps:]):
            args["model_metric"] = value
        history.clear()

    def _checkpoint(self) -> None:
        with trace.span("train.save", step=self.step):
            self.save()

    def _train_steps(self, steps: int, log: bool, save: bool):
        step_fn = self._train_step()
        t0 = time.time()
        history = []  # (loss, metric, span args) not yet drained to the host
        fetched: list[float] = []
        # drain in chunks: keeping one live device scalar per step for a
        # long run pins an unbounded number of small device buffers
        drain_every = 4096
        profile = _ProfileWindow(self)
        try:
            for _ in range(steps):
                with trace.span("train.step", step=self.step):
                    with trace.span("train.next_batch", step=self.step):
                        batch = self._next_batch(1)
                        rngs = self._rngs(self.step)
                    with profile.step():
                        loss, metric, args = self._dispatch(step_fn, rngs, batch)
                    self.step += 1
                    profile.stop_if_done(loss)
                    if log and self.step % self.cfg.log_steps == 0:
                        loss_v = float(loss)
                        dt = time.time() - t0
                        print(
                            f"step {self.step}: loss={loss_v:.4f} "
                            f"metric={float(metric):.4f} "
                            f"({self.step / dt:.1f} it/s)"
                        )
                    # keep losses (and the model's metric) on device — a
                    # float() here would force a blocking device→host
                    # round trip every step and serialize the pipeline
                    history.append((loss, metric, args))
                    if len(history) >= drain_every:
                        self._drain(history, fetched, concat=False)
                    if (
                        self.cfg.checkpoint_steps
                        and self.step % self.cfg.checkpoint_steps == 0
                    ):
                        self._checkpoint()
        finally:
            # a raising loop (dead shard, OOM, poisoned batch) must still
            # surface the losses fetched so far and leave a best-effort
            # checkpoint — previously both were silently dropped
            self._finish_train(history, fetched, profile, save)
        return fetched

    def _finish_train(self, history, fetched, profile, save, concat=False):
        """Shared train-loop epilogue, run from a `finally`: stop a live
        profiler trace, drain the on-device loss history, publish the
        losses fetched so far on `self.last_losses`, and save. When an
        exception is unwinding, the drain and the save are best-effort
        (the original error stays the one surfaced); on the clean path a
        save failure still raises."""
        import sys as _sys

        exc_live = _sys.exc_info()[0] is not None
        try:
            profile.stop(self.params)
        except Exception:
            pass
        if history:
            try:
                self._drain(history, fetched, concat)
            except Exception:
                if not exc_live:
                    raise
        self.last_losses = list(fetched)
        if save and self.params is not None:
            if exc_live:
                try:
                    self._checkpoint()
                except Exception as e:
                    print(
                        f"# estimator: best-effort checkpoint after a "
                        f"raising train loop failed: {e!r}",
                        file=_sys.stderr,
                    )
            else:
                self._checkpoint()

    def _train_scan(self, steps: int, k: int, log: bool, save: bool):
        """Driver for steps_per_call>1: each batch_fn() item is a K-stacked
        batch; one jitted dispatch advances K optimizer steps. A non-multiple
        remainder (steps % k) runs through the single-step path on slices of
        one final stacked item, so exactly `steps` updates are applied."""
        step_fn = self._train_step_scan()
        t0 = time.time()
        history = []
        fetched: list[float] = []
        drain_every = max(4096 // k, 1)
        calls, remainder = divmod(steps, k)
        profile = _ProfileWindow(self, min_steps=k)
        try:
            for _ in range(calls):
                with trace.span("train.step", step=self.step, steps=k):
                    with trace.span("train.next_batch", step=self.step):
                        batch = self._next_batch(k)
                        rngs = self._rngs_stacked(self.step, k)
                    with profile.step():
                        losses, metric, args = self._dispatch(step_fn, rngs, batch)
                    self.step += k
                    profile.stop_if_done(losses)
                    if log and self.step % max(self.cfg.log_steps, 1) < k:
                        dt = time.time() - t0
                        print(
                            f"step {self.step}: loss={float(losses[-1]):.4f} "
                            f"metric={float(metric):.4f} "
                            f"({self.step / dt:.1f} it/s)"
                        )
                    history.append((losses, metric, args))
                    if len(history) >= drain_every:
                        self._drain(history, fetched, concat=True)
                    if (
                        self.cfg.checkpoint_steps
                        and self.step % self.cfg.checkpoint_steps < k
                    ):
                        self._checkpoint()
            profile.stop(self.params)
            if remainder:
                single = self._train_step()
                with trace.span("train.step", step=self.step, steps=remainder):
                    with trace.span("train.next_batch", step=self.step):
                        item = (
                            (self._flow_keys(self.step, remainder),)
                            if self._device_flow is not None
                            else self._put(self.batch_fn(), stacked=True)
                        )
                    for i in range(remainder):
                        batch = jax.tree_util.tree_map(lambda x: x[i], item)
                        loss, metric, args = self._dispatch(
                            single, self._rngs(self.step), batch
                        )
                        self.step += 1
                        history.append((loss[None], metric, args))
        finally:
            # same contract as train(): a raising loop still drains the
            # fetched losses and leaves a best-effort checkpoint
            self._finish_train(history, fetched, profile, save, concat=True)
        return fetched[:steps]

    def _shared_apply_jit(self, kind: str, build):
        """Get-or-build an eval/embed program, rooted on the feature
        cache (the only instance object those programs read besides the
        model; its table is their `tables` argument)."""
        cache = _jit_cache(self.feature_cache)
        if cache is None:
            return build()
        key = (kind, self._model_key(), self._rng_names)
        with _JIT_CACHE_LOCK:
            if key not in cache:
                _jit_cache_put(cache, key, build())
            return cache[key]

    def evaluate(self, batches: Iterable[tuple]) -> dict:
        self._ensure_init()
        if self._jit_eval is None:
            model, fc = self.model, self.feature_cache
            self._jit_eval = self._shared_apply_jit(
                "eval",
                lambda: jax.jit(
                    lambda p, tables, rngs, *b: model.apply(
                        p,
                        *_hydrate_batch(_bound(fc, tables, "features"), b),
                        rngs=rngs,
                    )[1:4:2]
                ),
            )  # (loss, metric)
        name = getattr(self, "_metric_name", None)
        losses, metrics = [], []
        for batch in batches:
            batch = self._put(batch)
            loss, metric = self._jit_eval(
                self.params, self._tables(flow=False), self._rngs(0), *batch
            )
            if name is None:
                # the metric NAME is a static python string the jitted
                # program can't return; one eager forward fetches it, once
                # per Estimator (not per evaluate call)
                name = self._metric_name = self.model.apply(
                    self.params, *self._hydrate(batch), rngs=self._rngs(0)
                )[2]
            losses.append(float(loss))
            metrics.append(float(metric))
        return {
            "loss": float(np.mean(losses)) if losses else float("nan"),
            (name or "metric"): float(np.mean(metrics)) if metrics else float("nan"),
        }

    def embed_program(self):
        """The `(params, batch) -> embeddings` program `infer` runs —
        shared across instances via the feature-cache-rooted jit cache, and
        the program the serving runtime executes so served predictions are
        bit-identical to offline `infer` on the same checkpoint. Each call
        hands the jitted program (`.jitted`) the feature cache's table as
        it is then."""
        if self._jit_embed is None:
            model, fc = self.model, self.feature_cache

            def build():
                @jax.jit
                def embed(p, tables, b):
                    cache = _bound(fc, tables, "features")
                    return model.apply(
                        p, *_hydrate_batch(cache, (b,)), method=model.embed
                    )

                def program(params, batch):
                    return embed(params, _live_tables(None, None, fc), batch)

                program.jitted = embed
                return program

            if self.mesh is not None and fc is not None:
                # the shared program reads the table wherever it lies
                fc.replicate(self.mesh)
            self._jit_embed = self._shared_apply_jit("embed", build)
        return self._jit_embed

    def infer(
        self, batches: Iterable[tuple], ids: Iterable[np.ndarray], worker: int = 0
    ) -> tuple[np.ndarray, np.ndarray]:
        """Embeds batches; writes embedding_{worker}.npy + ids_{worker}.npy."""
        self._ensure_init()
        self.embed_program()
        embs, all_ids = [], []
        for batch, chunk_ids in zip(batches, ids):
            batch = self._put(batch)
            emb = np.asarray(self._jit_embed(self.params, batch[0]))
            embs.append(emb[: len(chunk_ids)])
            all_ids.append(np.asarray(chunk_ids))
        emb = np.concatenate(embs) if embs else np.zeros((0, 0))
        idv = np.concatenate(all_ids) if all_ids else np.zeros((0,), np.uint64)
        os.makedirs(self.cfg.model_dir, exist_ok=True)
        np.save(os.path.join(self.cfg.model_dir, f"embedding_{worker}.npy"), emb)
        np.save(os.path.join(self.cfg.model_dir, f"ids_{worker}.npy"), idv)
        return idv, emb

    def train_and_evaluate(self, eval_batches_fn, eval_every: int):
        """Alternate train/eval (base_estimator train_and_evaluate parity)."""
        results = []
        remaining = self.cfg.total_steps
        while remaining > 0:
            chunk = min(eval_every, remaining)
            self.train(chunk)
            results.append(self.evaluate(eval_batches_fn()))
            remaining -= chunk
        return results

    # -- checkpointing ---------------------------------------------------

    def save(self) -> str:
        """Commit one retained atomic checkpoint (`ckpt_<step>/` under
        model_dir: tmp + fsync + rename + COMMIT marker, keep-N GC).

        The old behavior — overwrite ONE fixed Orbax path with
        force=True — meant a kill -9 mid-save destroyed the only
        checkpoint in existence; now the previous complete checkpoint
        survives any crash point of this write. Returns the committed
        path."""
        from euler_tpu.training.checkpoint import CheckpointStore

        self._ensure_init()
        p_leaves, _ = jax.tree_util.tree_flatten(self.params)
        o_leaves, _ = jax.tree_util.tree_flatten(self.opt_state)
        store = CheckpointStore(
            self.cfg.model_dir, keep=self.cfg.keep_checkpoints
        )
        return store.save_leaves(
            self.step,
            [np.asarray(jax.device_get(x)) for x in p_leaves],
            [np.asarray(jax.device_get(x)) for x in o_leaves],
            {"seed": int(self.cfg.seed)},
        )

    def restore(self) -> bool:
        """Restore the newest COMPLETE retained checkpoint (torn dirs —
        a crash mid-save — are invisible by construction). False where
        the model_dir holds none."""
        from euler_tpu.training.checkpoint import CheckpointStore

        store = CheckpointStore(
            self.cfg.model_dir, keep=self.cfg.keep_checkpoints
        )
        step = store.latest_step()
        if step is None:
            return False
        self._ensure_init()
        ckpt = store.load(step)

        def onto(saved, live):
            leaves, tdef = jax.tree_util.tree_flatten(live)
            if len(saved) != len(leaves):
                raise ValueError(
                    f"checkpoint ckpt_{step:012d} carries {len(saved)} "
                    f"leaves where the live tree has {len(leaves)} — "
                    "model/optimizer config drifted from the saved run"
                )
            put = [
                jax.device_put(s, x.sharding)
                if isinstance(x, jax.Array)
                else jnp.asarray(s)
                for s, x in zip(saved, leaves)
            ]
            return jax.tree_util.tree_unflatten(tdef, put)

        self.params = onto(ckpt["params"], self.params)
        self.opt_state = onto(ckpt["opt_state"], self.opt_state)
        self.step = int(ckpt["step"])
        return True


def stack_batches(batch_fn: Callable[[], tuple], k: int) -> Callable[[], tuple]:
    """Wrap a batch source to return K batches stacked on a leading axis,
    for `EstimatorConfig.steps_per_call=K` scan training."""

    def fn():
        batches = [batch_fn() for _ in range(k)]
        try:
            return jax.tree_util.tree_map(lambda *xs: np.stack(xs), *batches)
        except ValueError:
            # the usual cause: a lean dataflow downgraded mid-window, so
            # some batches carry masks/edge_w arrays and others None.
            # Hydrating the lean ones host-side is exact (they satisfied
            # the lean invariants) and makes the window stackable.
            from euler_tpu.dataflow.base import upgrade_lean_host

            batches = [
                tuple(upgrade_lean_host(x) for x in bt) for bt in batches
            ]
            try:
                return jax.tree_util.tree_map(
                    lambda *xs: np.stack(xs), *batches
                )
            except ValueError as e:
                raise ValueError(
                    "steps_per_call>1 requires every batch in a window to "
                    "have identical pytree structure; got a mix that lean "
                    "hydration could not reconcile (a batch_fn with "
                    f"varying structure?). Original error: {e}"
                ) from e

    return fn


# ---- batch sources (Node/Edge estimator input_fn parity) ----------------


def _shard_failure_wrap(fn, on_shard_failure: str, max_skips: int):
    """Shard-failure policy for training readers: "raise" (default)
    surfaces the typed error; "skip" drops the failed BATCH and draws the
    next one, so a dead shard degrades epoch throughput (batches routed
    to surviving coordinators keep flowing) instead of killing the run.
    Bounded: more than `max_skips` CONSECUTIVE failures re-raises — a
    fully dead cluster must not spin forever. `wrapped.skipped` counts
    dropped batches (telemetry: proves degradation was visible, not
    silent)."""
    if on_shard_failure not in ("raise", "skip"):
        raise ValueError(f"on_shard_failure: {on_shard_failure!r}")
    if on_shard_failure == "raise":
        return fn

    from euler_tpu.distributed.errors import RpcError

    def wrapped():
        skips = 0
        while True:
            try:
                return fn()
            except RpcError as e:
                wrapped.skipped += 1
                skips += 1
                if skips > max_skips:
                    raise RpcError(
                        f"skip-batch policy gave up after {skips}"
                        f" consecutive failures: {e}"
                    ) from e

    wrapped.skipped = 0
    return wrapped


def pipelined_batches(
    flow,
    batch_size: int,
    depth: int = 4,
    node_type: int = -1,
    on_shard_failure: str = "raise",
    max_skips: int = 16,
) -> Callable[[], tuple]:
    """Remote batch source with `depth` overlapped sage_minibatch RPCs.

    The reference client overlaps requests through gRPC completion queues
    (query_proxy.cc:235-256); here a rolling window of Futures keeps the
    shard servers busy while the head batch is consumed, hiding one-RPC
    latency behind its successors. Falls back to sync flow.minibatch when
    the graph has no async surface (in-process graphs). Thread-safe: may
    be wrapped in a Prefetcher with multiple workers."""
    from collections import deque

    pending: deque = deque()
    lock = threading.Lock()
    sync_mode = [False]  # sticky downgrade: no async surface / old server

    def fn():
        with lock:
            if not sync_mode[0]:
                while len(pending) < max(depth, 1):
                    fut = flow.minibatch_async(batch_size, node_type)
                    if fut is None:  # no async surface → stay sync
                        sync_mode[0] = True
                        break
                    pending.append(fut)
            if sync_mode[0] and not pending:
                # sync minibatch under the lock: flow.rng is a shared
                # numpy Generator, not thread-safe across workers
                return (flow.minibatch(batch_size, node_type),)
            head = pending.popleft()
        try:
            return (head.result(),)
        except RuntimeError as e:
            if "unknown op" not in str(e):
                raise
            # pre-async server: downgrade stays sticky — stop refilling
            # the window with doomed RPCs, drop the in-flight ones
            with lock:
                sync_mode[0] = True
                pending.clear()
                return (flow.minibatch(batch_size, node_type),)

    return _shard_failure_wrap(fn, on_shard_failure, max_skips)


def node_batches(
    graph,
    flow,
    batch_size: int,
    node_type: int = -1,
    rng=None,
    on_shard_failure: str = "raise",
    max_skips: int = 16,
) -> Callable[[], tuple]:
    """Training source: sample root nodes per step
    (node_estimator.py:31-37). on_shard_failure="skip" drops batches that
    die on a failed shard instead of killing the epoch (bounded; see
    _shard_failure_wrap)."""
    rng = rng if rng is not None else np.random.default_rng()

    def fn():
        roots = graph.sample_node(batch_size, node_type, rng=rng)
        return (flow.query(roots),)

    return _shard_failure_wrap(fn, on_shard_failure, max_skips)


def edge_batches(
    graph, flow, batch_size: int, edge_type: int = -1, rng=None
) -> Callable[[], tuple]:
    """Training source over sampled edges: returns src-node batches with the
    dst id as positive context (edge_estimator parity)."""
    rng = rng if rng is not None else np.random.default_rng()

    def fn():
        edges = graph.sample_edge(batch_size, edge_type, rng=rng)
        return (flow.query(edges[:, 0]), flow.query(edges[:, 1]))

    return fn


def unsupervised_batches(
    graph,
    flow,
    batch_size: int,
    node_type: int = -1,
    edge_types=None,
    num_negs: int = 5,
    neg_type: int = -1,
    rng=None,
) -> Callable[[], tuple]:
    """(src, pos, negs) source for UnsuperviseModel (mp_utils/base.py:52-95):
    pos = sampled 1-hop neighbor of src, negs = globally sampled nodes."""
    rng = rng if rng is not None else np.random.default_rng()

    def fn():
        src = graph.sample_node(batch_size, node_type, rng=rng)
        nbr, _, _, mask, _ = graph.sample_neighbor(src, edge_types, 1, rng=rng)
        pos = np.where(mask[:, 0], nbr[:, 0], src)
        negs = graph.sample_node(batch_size * num_negs, neg_type, rng=rng)
        return (flow.query(src), flow.query(pos), flow.query(negs))

    return fn


def _padded_chunks(ids: np.ndarray, batch_size: int) -> Iterator[np.ndarray]:
    """Fixed-size id chunks; the last one pads by repeating its final id."""
    for i in range(0, len(ids), batch_size):
        chunk = ids[i : i + batch_size]
        if len(chunk) < batch_size:  # pad to keep shapes static
            chunk = np.concatenate(
                [chunk, np.repeat(chunk[-1:], batch_size - len(chunk))]
            )
        yield chunk


def read_sample_ids(path: str, column: int = 0) -> np.ndarray:
    """u64 root ids from a comma-separated sample file (one sample/line)."""
    from euler_tpu.utils.file_io import open_file

    with open_file(path, "r") as f:
        rows = [line.strip().split(",") for line in f if line.strip()]
    return np.asarray([np.uint64(r[column]) for r in rows], dtype=np.uint64)


def sample_file_batches(
    flow,
    path: str,
    batch_size: int,
    epochs: int = 1,
    column: int = 0,
) -> Iterator[tuple]:
    """Training source from comma-separated sample files
    (SampleEstimator parity, euler_estimator sample_estimator.py): each
    line holds CSV fields; `column` selects the root node id field. Yields
    padded fixed-size batches for `epochs` passes. The final batch repeats
    its last id to keep shapes static — for exact evaluation/inference over
    a sample file, pass `read_sample_ids(path)` to `id_batches`, whose id
    chunks identify the padding."""
    ids = read_sample_ids(path, column)
    for _ in range(epochs):
        for chunk in _padded_chunks(ids, batch_size):
            yield (flow.query(chunk),)


def id_batches(
    flow, ids: np.ndarray, batch_size: int
) -> tuple[Iterator[tuple], Iterator[np.ndarray]]:
    """Fixed-id evaluation/inference source (chunked, last chunk padded)."""
    ids = np.asarray(ids, dtype=np.uint64)

    def batches():
        for chunk in _padded_chunks(ids, batch_size):
            yield (flow.query(chunk),)

    def id_chunks():
        for i in range(0, len(ids), batch_size):
            yield ids[i : i + batch_size]

    return batches(), id_chunks()
