"""Typed YAML configuration system (the framework's front door).

Capability parity with reference ``torchbooster/config.py`` (628 LoC),
re-designed for a JAX/TPU runtime:

- ``#include`` preprocessor                     (ref config.py:47-87)
- string pseudo-annotation type resolution      (ref config.py:90-151)
  supporting ``list(int)``, ``tuple(float, float)``, comma-separated
  scalar strings, nested :class:`BaseConfig` subclasses resolved by name,
  extra-key warnings, and scalar→list coercion (fixing the reference's
  crash on scalar-for-list YAML, ref config.py:129 / offline.yml).
- ``BaseConfig.load`` single-config + sweep generator (ref config.py:274-301)
- hyperparameter sweeps via a SAFE expression grammar — the reference
  ``eval()``'s every string leaf (ref config.py:206, a noted security
  hazard); here only ``arange/linspace/logspace/geomspace/range`` calls
  and literal lists are recognized, parsed without ``eval``.
- bundled factory configs (ref config.py:304-617): Env, Loader, Optimizer,
  Scheduler, Dataset — each ``make()`` producing TPU-native runtime
  objects (mesh/shardings, host data pipeline, optax transforms, pure
  schedule fns) instead of CUDA/DDP objects.
"""
from __future__ import annotations

import ast
import builtins
import copy
import dataclasses
import itertools
import logging
import re
import sys
from dataclasses import dataclass
from pathlib import Path
from typing import Any, Callable, Generator, Iterable

import numpy as np
import yaml

# =========================================================================
# #include preprocessor (ref config.py:47-87)
# =========================================================================

INCLUDE_PATTERN = re.compile(r"^\s*#include\s+(.+?)\s*$")


def do_include(line: str) -> str | None:
    """Return the include target if ``line`` is a ``#include`` directive."""
    match = INCLUDE_PATTERN.match(line)
    return match.group(1) if match else None


def read_lines(path: str | Path, _stack: tuple[Path, ...] = ()) -> list[str]:
    """Read ``path`` splicing ``#include``d files in place, recursively.

    Include paths are resolved relative to the including file's directory
    (ref config.py:82,86). Circular include chains raise
    :class:`RecursionError` (the reference recurses forever until Python
    raises the same error; here the cycle is detected eagerly and reported
    with the offending chain — same exception type for test parity,
    ref test/test_config.py:40-43).
    """
    path = Path(path)
    resolved = path.resolve()
    if resolved in _stack:
        chain = " -> ".join(str(p) for p in (*_stack, resolved))
        raise RecursionError(f"circular #include chain: {chain}")
    lines: list[str] = []
    for line in path.read_text().splitlines():
        target = do_include(line)
        if target is not None:
            included = (path.parent / target).resolve()
            lines.extend(read_lines(included, (*_stack, resolved)))
        else:
            lines.append(line)
    return lines


# =========================================================================
# String pseudo-annotation type resolution (ref config.py:90-151)
# =========================================================================

_ANNOTATION_PATTERN = re.compile(r"^(\w+)\s*\((.*)\)$")


def _all_config_subclasses(cls: type) -> list[type]:
    out: list[type] = []
    for sub in cls.__subclasses__():
        out.append(sub)
        out.extend(_all_config_subclasses(sub))
    return out


def _lookup_type(name: str, owner: type) -> type:
    """Resolve a type name: builtins → owner module globals → BaseConfig
    subclasses by class name (ref config.py:132-138 — the subclass lookup
    is what lets user-defined config classes appear in YAML untouched)."""
    name = name.strip()
    if hasattr(builtins, name):
        return getattr(builtins, name)
    module = sys.modules.get(owner.__module__)
    if module is not None and hasattr(module, name):
        return getattr(module, name)
    for sub in _all_config_subclasses(BaseConfig):
        if sub.__name__ == name:
            return sub
    raise NameError(f"cannot resolve config type {name!r} for {owner.__name__}")


def _cast_scalar(field_type: type, value: Any, owner: type) -> Any:
    if value is None:
        return None
    if isinstance(field_type, type) and issubclass(field_type, BaseConfig):
        return field_type(**resolve_types(field_type, value or {}))
    if field_type is bool and isinstance(value, str):
        return value.strip().lower() in ("1", "true", "yes", "on")
    if field_type is Any:
        return value
    return field_type(value)


def _split_elements(value: Any) -> list[Any]:
    """Normalize a container field's YAML value into a list of elements.

    Accepts YAML lists/tuples, comma-separated strings (``decay: lin, cos``
    → ``["lin", "cos"]``, ref test/configs/full.yml), and bare scalars
    (coerced to a one-element list — fixes ref crash at config.py:129)."""
    if isinstance(value, str):
        return [part.strip() for part in value.split(",")]
    if isinstance(value, (list, tuple)):
        return list(value)
    return [value]


def resolve_types(cls: type, data: dict[str, Any] | None) -> dict[str, Any]:
    """Coerce raw YAML ``data`` into typed kwargs for dataclass ``cls``.

    Field annotations are *strings* (``from __future__ import annotations``)
    in a pseudo-syntax: ``int``, ``list(int)``, ``tuple(float, float)``,
    ``SomeConfig``. Container element types cycle over the data
    (ref config.py:127). Extra YAML keys warn, never fail
    (ref config.py:146-149)."""
    data = dict(data or {})
    fields = {field.name: field for field in dataclasses.fields(cls)}
    extra = sorted(set(data) - set(fields))
    if extra:
        logging.warning(
            "%s received extra config parameters %s (ignored)",
            cls.__name__, extra,
        )
    kwargs: dict[str, Any] = {}
    for name, field in fields.items():
        if name not in data:
            continue
        annotation = field.type if isinstance(field.type, str) else getattr(
            field.type, "__name__", str(field.type))
        kwargs[name] = _coerce(cls, annotation, data[name])
    return kwargs


def _coerce(owner: type, annotation: str, value: Any) -> Any:
    annotation = annotation.strip()
    if value is None:
        return None
    match = _ANNOTATION_PATTERN.match(annotation)
    if match:
        container_name, inner = match.group(1), match.group(2)
        container = _lookup_type(container_name, owner)
        element_names = [e for e in (s.strip() for s in inner.split(",")) if e]
        element_types = [_lookup_type(e, owner) for e in element_names] or [str]
        elements = _split_elements(value)
        cast = [
            _cast_scalar(el_type, el, owner)
            for el_type, el in zip(itertools.cycle(element_types), elements)
        ]
        return container(cast)
    field_type = _lookup_type(annotation, owner)
    return _cast_scalar(field_type, value, owner)


# =========================================================================
# Safe sweep expression grammar (replaces ref eval(), config.py:186-258)
# =========================================================================

_SWEEP_CALL = re.compile(r"^\s*(arange|linspace|logspace|geomspace|range)\s*\((.*)\)\s*$")


def parse_sweep(text: str) -> list[Any] | None:
    """Parse a sweep expression from a YAML string leaf; ``None`` if the
    string is not a sweep. Recognized forms (all parsed without ``eval``):

    - ``arange(start, stop[, step])`` / ``linspace(a, b, n)`` /
      ``logspace(a, b, n)`` / ``geomspace(a, b, n)`` — numpy semantics
      (the reference imports ``numpy.arange`` into eval scope for this,
      ref config.py:204).
    - ``range(...)`` — python semantics.
    - a quoted literal list, e.g. ``"[1, 2, 3]"``.
    """
    if not isinstance(text, str):
        return None
    stripped = text.strip()
    if stripped.startswith("[") and stripped.endswith("]"):
        try:
            parsed = ast.literal_eval(stripped)
        except (ValueError, SyntaxError):
            return None
        return list(parsed) if isinstance(parsed, (list, tuple)) else None
    match = _SWEEP_CALL.match(stripped)
    if not match:
        return None
    func, args_text = match.groups()
    try:
        args = [ast.literal_eval(arg.strip()) for arg in args_text.split(",") if arg.strip()]
    except (ValueError, SyntaxError):
        return None
    if not all(isinstance(a, (int, float)) for a in args):
        return None
    try:
        if func == "range":
            return list(range(*[int(a) for a in args]))
        values = getattr(np, func)(*args)
    except (TypeError, ValueError):
        return None
    return [v.item() for v in np.asarray(values).ravel()]


class HyperParameterConfig:
    """Cartesian-product sweep generator over YAML string-leaf axes
    (ref config.py:186-258, odometer loop at :224-232 → itertools.product
    here). Each combination yields a fully-typed config instance."""

    def __init__(self, cls: type, stream: str):
        self.cls = cls
        self.data = yaml.safe_load(stream) or {}
        self.axes: list[tuple[tuple[Any, ...], list[Any]]] = []
        self._find_hparams(self.data, ())

    def _find_hparams(self, node: Any, path: tuple[Any, ...]) -> None:
        if isinstance(node, dict):
            for key, value in node.items():
                self._find_hparams(value, (*path, key))
        elif isinstance(node, list):
            for idx, value in enumerate(node):
                self._find_hparams(value, (*path, idx))
        elif isinstance(node, str):
            values = parse_sweep(node)
            if values is not None:
                self.axes.append((path, values))

    @staticmethod
    def _set(data: Any, path: tuple[Any, ...], value: Any) -> None:
        node = data
        for key in path[:-1]:
            node = node[key]
        node[path[-1]] = value

    def gen_cfg(self) -> Generator[Any, None, None]:
        if not self.axes:
            yield self.cls(**resolve_types(self.cls, copy.deepcopy(self.data)))
            return
        for combo in itertools.product(*(values for _, values in self.axes)):
            data = copy.deepcopy(self.data)
            for (path, _), value in zip(self.axes, combo):
                self._set(data, path, value)
            yield self.cls(**resolve_types(self.cls, data))


# =========================================================================
# BaseConfig (ref config.py:261-301)
# =========================================================================

@dataclass
class BaseConfig:
    """Base class for typed YAML configs. Subclasses are ``@dataclass``es
    whose field annotations use the pseudo-syntax described in
    :func:`resolve_types`, and override :meth:`make` to build the runtime
    object the config describes (ref config.py:261-301)."""

    def make(self, *args: Any, **kwargs: Any) -> Any:
        raise NotImplementedError("BaseConfig subclasses must implement make()")

    @classmethod
    def load(cls, path: str | Path, hyperparams: bool = False):
        """Load ``path`` → one config, or a generator of configs when
        ``hyperparams=True`` (ref config.py:274-301)."""
        stream = "\n".join(read_lines(path))
        if hyperparams:
            return HyperParameterConfig(cls, stream).gen_cfg()
        data = yaml.safe_load(stream) or {}
        return cls(**resolve_types(cls, data))


# =========================================================================
# Bundled runtime configs (ref config.py:304-617)
# =========================================================================

@dataclass
class EnvConfig(BaseConfig):
    """Execution environment: devices, precision, mesh topology.

    TPU-native analogue of the reference ``EnvironementConfig``
    (ref config.py:304-334; the [sic] spelling is kept as an alias below).
    ``fp16``/``n_gpu`` remain as parity aliases; the native fields are
    ``precision`` (bf16 is the TPU story — no loss scaling needed) and
    ``n_devices``/``mesh``. ``dist_url`` becomes the multi-host JAX
    coordinator address (ref dist_url, config.py:315)."""

    distributed: bool = False
    fp16: bool = False                 # parity alias → bf16 compute on TPU
    precision: str = ""                # "" (auto) | "fp32" | "bf16"
    n_gpu: int = -1                    # parity alias for n_devices (-1 unset)
    n_devices: int = 0                 # 0 → all local devices
    n_machine: int = 1
    machine_rank: int = 0
    dist_url: str = "auto"             # jax.distributed coordinator ("auto" = single host)
    mesh: str = "dp"                   # axis spec: "dp" | "dp:2,tp:4" | "dp,fsdp,tp,sp"

    def compute_dtype(self):
        import jax.numpy as jnp

        if self.precision == "bf16" or (not self.precision and self.fp16):
            return jnp.bfloat16
        return jnp.float32

    def make(self, *args: Any, model: Any = None,
             rules: Any = None) -> Any:
        """Place objects into the environment (ref ``to_env``,
        config.py:154-182): array pytrees are device_put over the mesh
        (params — the DP analogue of DDP's initial broadcast, ref
        config.py:178); use :meth:`shard_batch` for data. A single
        argument returns the object, several return a list
        (ref config.py:333-334).

        Pass ``model=`` (anything carrying ``SHARDING_RULES``) or
        ``rules=`` to lay parameters/TrainStates out by the rule table
        instead of replicating — the YAML ``mesh:`` line then IS the
        parallelism config ("that flip is the product", SURVEY §7);
        axes absent from the mesh are filtered, so the same call works
        from 1 device through dp×fsdp×tp."""
        from torchbooster_tpu import distributed as dist

        if rules is None and model is not None:
            rules = getattr(model, "SHARDING_RULES", None)
        mesh = dist.get_mesh(self)
        if rules is None:
            # the one-switch contract cuts both ways: a multi-axis mesh
            # with nothing to lay weights out by silently replicates —
            # say so loudly instead of letting a "fsdp:8" YAML no-op
            param_axes = [a for a, s in mesh.shape.items()
                          if a != "dp" and s > 1]
            if param_axes:
                logging.warning(
                    "mesh %r has parameter-sharding axes %s but no "
                    "sharding rules were provided — parameters will "
                    "fully replicate on every device. Pass "
                    "make(..., model=<class with SHARDING_RULES>) or "
                    "rules=[...] to shard.", self.mesh, param_axes)
        placed = [dist.to_env(obj, mesh, rules=rules) for obj in args]
        return placed[0] if len(placed) == 1 else placed

    def shard_batch(self, batch: Any) -> Any:
        """Shard a host batch along its leading axis over the mesh's data
        axes (the TPU analogue of per-rank batches + H2D copy)."""
        from torchbooster_tpu import distributed as dist

        return dist.shard_batch(batch, dist.get_mesh(self))


# Reference-parity alias — the typo is part of the reference's public API
# surface (ref config.py:304).
EnvironementConfig = EnvConfig


@dataclass
class LoaderConfig(BaseConfig):
    """Host data-loader settings (ref config.py:337-379). ``pin_memory``
    is accepted for parity but is a no-op: host→device transfer is handled
    by the prefetch-to-device iterator instead."""

    batch_size: int = 32
    num_workers: int = 0
    pin_memory: bool = False
    drop_last: bool = True             # static shapes: avoid remainder recompiles
    prefetch: int = 2                  # device prefetch depth

    def make(
        self,
        dataset: Any,
        shuffle: bool = True,
        distributed: bool = False,
        collate_fn: Callable | None = None,
        seed: int = 0,
    ) -> Any:
        """Build the host pipeline → per-process shard → batches iterator
        (ref config.py:348-379; the DistributedSampler at ref
        distributed.py:78-98 becomes process_index-keyed sharding)."""
        from torchbooster_tpu.data import DataLoader

        return DataLoader(
            dataset,
            batch_size=self.batch_size,
            shuffle=shuffle,
            distributed=distributed,
            drop_last=self.drop_last,
            num_workers=self.num_workers,
            prefetch=self.prefetch,
            collate_fn=collate_fn,
            seed=seed,
        )


def _sgd_momentum_dampened(momentum: float, dampening: float):
    """torch.optim.SGD's momentum buffer with dampening: after the
    first accumulation ``buf ← μ·buf + (1−d)·g``, but the buffer is
    *initialized to the raw gradient* — the ``(1−d)`` factor does not
    apply on the first step (torch sgd docs; ref config.py:389-396
    forwarded this knob to torch, so parity means matching torch's
    semantics exactly, not optax.trace's zeros-init which would scale
    the very first update by ``1−d``)."""
    import jax
    import jax.numpy as jnp
    import optax

    def init(params):
        return {"count": jnp.zeros([], jnp.int32),
                "trace": jax.tree.map(jnp.zeros_like, params)}

    def update(updates, state, params=None):
        del params
        first = state["count"] == 0
        trace = jax.tree.map(
            lambda t, g: jnp.where(first, g,
                                   momentum * t + (1.0 - dampening) * g),
            state["trace"], updates)
        return trace, {"count": state["count"] + 1, "trace": trace}

    return optax.GradientTransformation(init, update)


def _scale_by_amsgrad_torch(b1: float, b2: float, eps: float):
    """AMSGrad second-moment rule with torch's exact semantics: the
    running max is taken over the *uncorrected* ``v_t`` and the bias
    correction divides the max afterwards, with eps added outside
    (torch.optim.Adam(amsgrad=True) docs). optax.scale_by_amsgrad maxes
    the bias-corrected v̂ and puts eps inside the sqrt — ~1% drift over
    a handful of steps, enough to break checkpoint-level parity with
    the reference's torch training runs (ref config.py:397-403)."""
    import jax
    import jax.numpy as jnp
    import optax

    def init(params):
        zeros = lambda: jax.tree.map(jnp.zeros_like, params)
        return {"count": jnp.zeros([], jnp.int32), "mu": zeros(),
                "nu": zeros(), "nu_max": zeros()}

    def update(updates, state, params=None):
        del params
        count = state["count"] + 1
        t = count.astype(jnp.float32)
        mu = jax.tree.map(lambda m, g: b1 * m + (1 - b1) * g,
                          state["mu"], updates)
        nu = jax.tree.map(lambda v, g: b2 * v + (1 - b2) * g * g,
                          state["nu"], updates)
        nu_max = jax.tree.map(jnp.maximum, state["nu_max"], nu)
        bc1, bc2 = 1 - b1 ** t, 1 - b2 ** t
        out = jax.tree.map(
            lambda m, v: (m / bc1) / (jnp.sqrt(v / bc2) + eps),
            mu, nu_max)
        return out, {"count": count, "mu": mu, "nu": nu,
                     "nu_max": nu_max}

    return optax.GradientTransformation(init, update)


@dataclass
class OptimizerConfig(BaseConfig):
    """Optimizer factory (ref config.py:382-438, names sgd/adamw there).

    Builds an ``optax`` gradient transformation wrapped in
    ``inject_hyperparams`` so the learning rate lives in the optimizer
    state (inspectable + checkpointable, like torch param_groups). The
    union-of-hyperparams field style follows the reference."""

    name: str = "adamw"                # sgd | adam | adamw | lamb | lion | adafactor
    lr: float = 1e-3
    momentum: float = 0.0
    dampening: float = 0.0             # torch-SGD momentum dampening (honored)
    betas: tuple(float, float) = (0.9, 0.999)
    eps: float = 1e-8
    weight_decay: float = 0.0
    nesterov: bool = False
    amsgrad: bool = False              # adam/adamw max-of-v̂ variant (honored)
    # adaptive gradient clipping λ (0 = off): clips each unit's grad to
    # λ·‖W‖ before the update — the published companion to norm-free
    # models (models/resnet.py norm="ws"), whose sharper loss surface
    # diverges under large adaptive LRs without it
    agc: float = 0.0
    # decay matrices only: masks weight decay off every rank-≤1 param
    # (biases, norm scales, per-channel gains) — the standard rule the
    # reference's torch AdamW applied to everything indiscriminately
    decay_matrices_only: bool = False

    def make(self, schedule: Callable[[Any], Any] | None = None):
        """Return an ``optax.GradientTransformation``. When ``schedule``
        (a pure step→lr fn, see :mod:`torchbooster_tpu.scheduler`) is
        given, it drives the injected ``learning_rate`` hyperparameter —
        replacing the reference's in-place param-group mutation
        (ref scheduler.py:162-163)."""
        import optax

        lr = schedule if schedule is not None else self.lr
        name = self.name.lower()
        # mask=callable: optax evaluates it on the param pytree at
        # init, so the config needs no access to the model here
        mask = None
        if self.decay_matrices_only:
            import jax

            mask = lambda params: jax.tree.map(lambda p: p.ndim > 1,
                                               params)
        if name == "sgd":
            if self.nesterov and (self.dampening or not self.momentum):
                # torch.optim.SGD rejects both combinations at
                # construction (ref honored torch's knob set,
                # ref config.py:389-396) — mirror it rather than
                # silently dropping the knob
                raise ValueError(
                    "nesterov requires a momentum and zero dampening")
            if self.momentum and self.dampening:
                factory = lambda learning_rate: optax.chain(
                    _sgd_momentum_dampened(self.momentum,
                                           self.dampening),
                    optax.scale_by_learning_rate(learning_rate))
            else:
                factory = lambda learning_rate: optax.sgd(
                    learning_rate, momentum=self.momentum or None,
                    nesterov=self.nesterov)
            if self.weight_decay:
                factory_inner = factory
                factory = lambda learning_rate: optax.chain(
                    optax.add_decayed_weights(self.weight_decay,
                                              mask=mask),
                    factory_inner(learning_rate))
        elif name == "adam":
            if self.amsgrad:
                # ref config.py:397-403 passed amsgrad through to
                # torch.optim.Adam; torch-exact rule, see helper
                factory = lambda learning_rate: optax.chain(
                    _scale_by_amsgrad_torch(
                        self.betas[0], self.betas[1], self.eps),
                    optax.scale_by_learning_rate(learning_rate))
            else:
                factory = lambda learning_rate: optax.adam(
                    learning_rate, b1=self.betas[0], b2=self.betas[1],
                    eps=self.eps)
        elif name == "adamw":
            if self.amsgrad:
                # optax.adamw has no amsgrad flag: rebuild its exact
                # chain (scale_by_adam → decoupled decay → lr) with the
                # torch-semantics max-of-v rule swapped in
                factory = lambda learning_rate: optax.chain(
                    _scale_by_amsgrad_torch(
                        self.betas[0], self.betas[1], self.eps),
                    optax.add_decayed_weights(self.weight_decay,
                                              mask=mask),
                    optax.scale_by_learning_rate(learning_rate))
            else:
                factory = lambda learning_rate: optax.adamw(
                    learning_rate, b1=self.betas[0], b2=self.betas[1],
                    eps=self.eps, weight_decay=self.weight_decay,
                    mask=mask)
        elif name == "lamb":
            factory = lambda learning_rate: optax.lamb(
                learning_rate, b1=self.betas[0], b2=self.betas[1],
                eps=self.eps, weight_decay=self.weight_decay, mask=mask)
        elif name == "lion":
            factory = lambda learning_rate: optax.lion(
                learning_rate, b1=self.betas[0], b2=self.betas[1],
                weight_decay=self.weight_decay, mask=mask)
        elif name == "adafactor":
            factory = lambda learning_rate: optax.adafactor(learning_rate)
        else:
            # ref config.py:438 raises NameError on unknown optimizer names
            raise NameError(f"unknown optimizer {self.name!r}")
        if self.agc:
            inner_factory = factory
            factory = lambda learning_rate: optax.chain(
                optax.adaptive_grad_clip(self.agc),
                inner_factory(learning_rate))
        return optax.inject_hyperparams(factory)(learning_rate=lr)


@dataclass
class SchedulerConfig(BaseConfig):
    """LR schedule factory (ref config.py:441-466, name ∈ {cycle}).
    Produces a *pure function of the step count* — the functional
    replacement for the reference's stateful ``CycleScheduler``."""

    name: str = "cycle"
    n_iter: int = 0
    initial_multiplier: float = 4e-2
    final_multiplier: float = 1e-5
    warmup: int = 0
    plateau: int = 0
    decay: tuple(str, str) = ("cos", "cos")

    def make(self, optim: OptimizerConfig):
        if self.name.lower() != "cycle":
            # ref config.py:466 raises NameError on unknown scheduler names
            raise NameError(f"unknown scheduler {self.name!r}")
        from torchbooster_tpu.scheduler import CycleScheduler
        return CycleScheduler(
            lr=optim.lr,
            n_iter=self.n_iter,
            initial_multiplier=self.initial_multiplier,
            final_multiplier=self.final_multiplier,
            warmup=self.warmup,
            plateau=self.plateau,
            decay=tuple(self.decay),
        )


@dataclass
class FrontendConfig(BaseConfig):
    """The serving front door (torchbooster_tpu/serving/frontend):
    scheduler policy + the asyncio OpenAI-compatible HTTP server.
    Nested under ``serving:`` as its ``frontend:`` sub-block. No
    reference analogue — this is the request-facing half of the
    "millions of users" north-star item.

    ``policy`` selects the scheduler: ``fcfs`` (default — byte-for-
    byte the pre-frontend batcher: strict arrival order, never shed,
    youngest preemption victim) or ``slo`` (deadline-driven:
    earliest-slack-first admission over ``classes``, load shedding
    with HTTP 429 + Retry-After when a TTFT deadline is already
    unmeetable, preemption victims by re-admission cost — a
    prefix-cached victim is nearly free to re-seat).

    ``classes`` is the priority-class table as a compact spec string
    (the mesh-spec idiom): ``"name:ttft_ms:tpot_ms,..."`` in priority
    order (first = highest), 0 disabling that deadline — e.g.
    ``"interactive:250:60,batch:5000:0"``. ``default_class`` names
    the class of requests that don't send one (defaults to the first
    listed). ``shed_grace`` scales the shed threshold (1.0 = shed
    exactly when the estimate says the deadline is lost; higher
    sheds later). ``max_queue`` bounds the HTTP submit queue —
    beyond it requests get 429 before touching the scheduler.

    ``capture_path`` turns on workload capture (serving/loadgen):
    every accepted submit is recorded — arrival offset, prompt ids,
    priority class, deadline, output budget, and the client's cancel
    offset, keyed by ``request_id`` — and the versioned JSONL trace
    lands at that path when the server stops, ready for the replay
    drivers (and the ``loadgen:`` block) to re-offer verbatim.
    ``capture_scrub: true`` never persists prompt CONTENT: each
    record keeps only a length + regeneration-seed recipe.

    The server itself is stdlib asyncio; install the ``[serve]``
    extra and call ``frontend.server.install_uvloop()`` for the
    optional event-loop swap. See docs/serving.md for the request
    lifecycle, API surface, and the backpressure contract.
    """

    host: str = "127.0.0.1"
    port: int = 8000                   # 0 = ephemeral (tests/benches)
    policy: str = "fcfs"               # fcfs | slo
    classes: str = ""                  # "name:ttft_ms:tpot_ms,..."
    default_class: str = ""            # "" = first listed class
    shed_grace: float = 1.0
    max_queue: int = 64
    capture_path: str = ""             # "" = no workload capture
    capture_scrub: bool = False        # capture recipes, not prompts

    def make_policy(self) -> Any:
        """Build the scheduler policy object the batcher consumes."""
        from torchbooster_tpu.serving.frontend import (
            FCFSPolicy, SLOPolicy, parse_classes)

        if self.policy == "fcfs":
            return FCFSPolicy()
        if self.policy == "slo":
            return SLOPolicy(parse_classes(self.classes),
                             default=self.default_class,
                             shed_grace=self.shed_grace)
        raise ValueError(
            f"frontend.policy must be 'fcfs' or 'slo', got "
            f"{self.policy!r}")

    def make(self, batcher: Any, codec: Any = None) -> Any:
        """Build the :class:`~torchbooster_tpu.serving.frontend.
        ServingFrontend` over an already-built batcher (normally
        ``ServingConfig.make(...)``, which installs this block's
        policy). ``await frontend.start()`` binds and serves."""
        from torchbooster_tpu.serving.frontend import ServingFrontend

        return ServingFrontend(batcher, host=self.host,
                               port=self.port, codec=codec,
                               max_queue=self.max_queue,
                               capture_path=self.capture_path or None,
                               capture_scrub=self.capture_scrub)


@dataclass
class HostSpillConfig(BaseConfig):
    """The KV-cache host spill tier (PR 16), nested under
    ``serving:`` as its ``host_spill:`` sub-block. No reference
    analogue — this is the memory hierarchy under the paged prefix
    cache.

    YAML block::

        serving:
          host_spill:
            enabled: true      # demote evicted prefix pages to host
            budget_mb: 64.0    # host-pool LRU byte budget

    ``enabled: true`` (needs ``prefix_cache: true``) turns LRU
    eviction of registered prefix pages into DEMOTION: the page's
    K/V quantize to int8 (+ fp32 per-(token, head) scales —
    ``models/gpt._quantize_kv``'s exact shape; int8 pools copy
    losslessly) into a host-DRAM pool bounded by ``budget_mb``, and
    a later request matching the chain promotes them back through
    one compiled fixed-shape H2D write instead of recomputing
    prefill — TTFT on a host hit pays PCIe stream time, not FLOPs
    (docs/performance.md "Page spill tier" has the roofline and the
    break-even prefix length). Off (the default), eviction frees
    pages exactly as PR 4 shipped it, and no staging buffers exist.
    """

    enabled: bool = False              # demote instead of free
    budget_mb: float = 64.0            # host LRU pool byte budget


@dataclass
class StructuredConfig(BaseConfig):
    """Structured generation (serving/structured), nested under
    ``serving:`` as its ``structured:`` sub-block. No reference
    analogue — this is the grammar/JSON-schema-constrained decoding
    surface over the paged engine.

    YAML block::

        serving:
          structured:
            enabled: true      # accept constraining response_format

    ``enabled: true`` builds the engine with the token-DFA machinery:
    requests may carry an OpenAI ``response_format``
    (``json_object`` | ``json_schema`` | ``regex``), compiled ONCE
    per schema into per-state allowed-token masks and enforced per
    slot as a fixed-shape legality mask threaded through the compiled
    decode/verify steps as a trailing VALUE operand — zero
    recompiles, exact token parity for unconstrained traffic, and
    full composition with speculative decoding and ``n``/``best_of``
    parallel sampling. Constraining requests require an ``eos_id``
    (the automaton terminates by forcing EOS at an accepting state).
    Off (the default), a constraining ``response_format`` is rejected
    at submit (HTTP 400) and the engine is bit-for-bit the
    unconstrained one. See docs/serving.md "Structured generation".
    """

    enabled: bool = False              # token-DFA constrained decoding


@dataclass
class WeightsConfig(BaseConfig):
    """Quantized weight serving (models/quant.py), nested under
    ``serving:`` as its ``weights:`` sub-block. No reference analogue
    — this narrows the decode roofline's WEIGHT stream the way
    ``cache_dtype: int8`` narrowed the KV stream.

    YAML block::

        serving:
          weights:
            dtype: int8        # bf16 (off) | int8 | int4
            group_size: 64     # int4 input-axis scale group

    ``dtype: int8`` quantizes every block dense kernel per-output-
    channel (symmetric absmax, scales factored out of the dot) and
    the embedding table per-row at engine build time — ONE host-side
    pass, then every compiled step streams 1 byte per weight and
    widens inside the matmul's operand read; greedy decode stays
    token-identical in practice (``tests/test_quant_lora.py::
    test_int8_paged_matches_fullprec_and_dense``). ``dtype: int4`` packs two values per byte with
    per-``group_size``-input-rows scales — 0.5 byte/elem at a real
    (bounded, documented) rounding cost; ``group_size`` must be even
    and divide every kernel's input dim. ``bf16`` (the default) is a
    no-op: params pass through untouched and every compiled artifact
    is byte-identical to the pre-feature engine. Composes with int8
    KV, tp sharding (scales shard beside their kernels), speculative
    verify, and the pallas backend — docs/performance.md "Quantized-
    weight roofline" has the bytes/step model and crossover.
    """

    dtype: str = "bf16"                # bf16 (off) | int8 | int4
    group_size: int = 64               # int4 scale group (input rows)

    def quantize(self, params: Any) -> Any:
        """Apply this block to a params tree (identity at bf16)."""
        if self.dtype in ("", "bf16"):
            return params
        from torchbooster_tpu.models.quant import quantize_params

        return quantize_params(params, self.dtype,
                               group_size=self.group_size)


@dataclass
class AdaptersConfig(BaseConfig):
    """Batched multi-LoRA serving (serving/adapters.py), nested under
    ``serving:`` as its ``adapters:`` sub-block. No reference
    analogue — this is the many-tenants-one-pool surface.

    YAML block::

        serving:
          adapters:
            rank: 8            # 0 = off; the trace-fixed LoRA rank
            max_live: 4        # device lanes (concurrent adapters)

    ``rank > 0`` builds the engine with ``max_live + 1`` device
    adapter LANES (lane 0 = the all-zero base adapter) on the
    attention projections: requests naming an adapter (the API
    ``model`` field) decode with its ranked delta gathered per slot
    each step, so one batch serves many adapters with ZERO
    recompiles across hot-load/evict churn (lane ids are traced
    values; the one fixed-shape lane writer compiles once). Register
    adapter weights at runtime through
    ``batcher.engine.adapters.register(name, weights)``; unknown
    names are rejected at submit (HTTP 400). Smaller-rank adapters
    zero-pad to ``rank``. Off (the default), no lora operand crosses
    the jit boundary and every compiled artifact is byte-identical
    to the pre-feature engine.
    """

    rank: int = 0                      # 0 = off; trace-fixed rank
    max_live: int = 4                  # device adapter lanes


@dataclass
class RouterHealthConfig(BaseConfig):
    """Per-replica health scoring (serving/router/health.py), nested
    under ``router:`` as its ``health:`` sub-block. No reference
    analogue — this is the fleet signal plane's replica scorer.

    YAML block::

        router:
          health:
            enabled: true        # observe replica health every step
            every: 8             # fleet steps between observations
            degrade_after: 2     # consecutive bad obs per level down
            recover_after: 4     # consecutive clean obs per level up
            queue_limit: 32      # queue-depth strike threshold
            min_free_pages: 0    # claimable-pages strike threshold
            stale_s: 2.0         # frozen-step_seq staleness window
            degraded_weight: 4.0   # health_aware score multiplier
            unhealthy_weight: 16.0 # health_aware score multiplier

    ``enabled: true`` attaches a
    :class:`~torchbooster_tpu.serving.router.FleetHealth` scorer to
    the fleet: every ``every`` fleet steps it folds flight-recorder
    anomalies (stall watchdog hits, recompiles), queue depth,
    claimable pages, and readiness staleness into a hysteretic
    healthy/degraded/unhealthy state per replica, exported as
    ``router_replica_health{replica}``. The scorer only OBSERVES;
    routing consults it solely under ``router.health_aware`` (see
    :class:`RouterConfig`). Off (the default), no scorer exists and
    the fleet's step loop is unchanged.
    """

    enabled: bool = False              # build the FleetHealth scorer
    every: int = 8                     # fleet steps per observation
    degrade_after: int = 2             # bad obs per level down
    recover_after: int = 4             # clean obs per level up
    queue_limit: int = 32              # queue-depth strike threshold
    min_free_pages: int = 0            # claimable-pages threshold
    stale_s: float = 2.0               # readiness staleness window
    degraded_weight: float = 4.0       # health_aware multiplier
    unhealthy_weight: float = 16.0     # health_aware multiplier

    def make(self) -> Any:
        """Build the :class:`FleetHealth` scorer (``None`` when
        disabled)."""
        if not self.enabled:
            return None
        from torchbooster_tpu.serving.router import FleetHealth

        return FleetHealth(
            every=self.every,
            degrade_after=self.degrade_after,
            recover_after=self.recover_after,
            queue_limit=self.queue_limit,
            min_free_pages=self.min_free_pages,
            stale_s=self.stale_s,
            degraded_weight=self.degraded_weight,
            unhealthy_weight=self.unhealthy_weight)


@dataclass
class DisaggConfig(BaseConfig):
    """Prefill/decode disaggregation (torchbooster_tpu/serving/
    disagg.py). Nested under ``serving:`` as its ``disagg:``
    sub-block. No reference analogue — this is the DistServe/
    Splitwise split applied to the paged engine.

    ``enabled: true`` makes ``ServingConfig.make`` return a
    :class:`~torchbooster_tpu.serving.disagg.DisaggPair` instead of a
    single batcher: a dedicated PREFILL engine (``prefill_only`` —
    its decode paths raise) plus the normal decode batcher, joined by
    a framed KV page stream in the host-spill demotion format (int8
    K/V + fp32 per-(layer, token, head) scales). Requests with at
    least ``min_prefill_pages`` full prompt pages prefill on the
    prefill pool and enter the decode pool through its host spill
    tier's promotion lane — zero new decode compiles; shorter ones
    go straight to the decode batcher. Needs ``prefix_cache: true``
    and ``host_spill.enabled: true`` (the stream lands in the host
    pool) and a single-replica router block (disaggregate AND
    replicate by building the fleet directly).

    ``prefill_n_pages`` / ``prefill_max_slots`` size the prefill
    pool independently (0 = inherit the serving geometry) — prefill
    needs pages for one long prompt at a time, not for a decode
    working set.
    """

    enabled: bool = False              # split prefill/decode pools
    min_prefill_pages: int = 1         # full pages to route long
    prefill_n_pages: int = 0           # 0 = serving.n_pages
    prefill_max_slots: int = 0         # 0 = serving.max_slots


@dataclass
class RouterConfig(BaseConfig):
    """The engine-fleet router (torchbooster_tpu/serving/router):
    N data-parallel engine replicas behind one front door. Nested
    under ``serving:`` as its ``router:`` sub-block. No reference
    analogue — this is ROADMAP item 2's replica scale-out.

    ``n_replicas: 1`` (the default) changes nothing: ``ServingConfig.
    make`` returns the plain single batcher, bit-for-bit. With
    ``n_replicas > 1`` it builds N identical engines + batchers
    (sharing the model params and ONE scheduler-policy table) and
    returns an :class:`~torchbooster_tpu.serving.router.EngineFleet`
    — which quacks like a batcher, so ``frontend.make(fleet)`` serves
    it over HTTP and ``replay_inprocess(fleet, ...)`` replays
    captures against it unchanged.

    ``policy`` picks the routing decision: ``round_robin`` (the
    control — live replicas in a fixed cycle) or ``affinity`` (the
    default — hash the request's page-aligned prompt prefix, at most
    ``affinity_pages`` full pages of it, into a replica-affinity map
    so tenants sharing a system prompt land where their prefix-cache
    pages are warm; keyless requests and spills route by least
    expected slack over per-replica queue depth × EWMA step
    estimates). ``spill_queue`` is the hot-prefix protection: when
    the mapped replica's queue sits that much deeper than the
    shallowest live one, the request spills to the least-loaded
    replica instead (the map is untouched — traffic returns home
    once the queue drains).

    ``rebalance_queue > 0`` turns on sustained-hot-spot readmission:
    after ``rebalance_after`` consecutive steps with the deepest
    live queue more than ``rebalance_queue`` over the shallowest,
    QUEUED requests migrate off the hot replica (the cheap end of
    the readmission-cost scale — no engine state moves). Replica
    DEATH readmission is always on: a replica whose step raises is
    buried and its queued + in-flight requests re-admit elsewhere
    with their generated tokens folded into their prompts (nothing
    lost, nothing duplicated). See docs/serving.md "The engine
    fleet" for the full contract.

    ``directory: true`` (the default) maintains the fleet-wide
    PREFIX DIRECTORY (PR 16): chain-key -> {replica, tier} from every
    replica's page-tier events, consulted by the affinity policy on a
    map miss so a re-arriving tenant routes to whichever replica
    actually holds its pages (HBM- or host-tier) instead of
    recomputing; replica death purges the dead entries (the
    ``router_directory_evictions`` counter) and rescues its host-tier
    chains onto a survivor. ``directory: false`` is the A/B control.

    ``replicas`` (PR 20) builds a MIXED fleet by explicit spec
    instead of ``n_replicas`` identical local ones: each entry is
    either the literal ``inproc`` (build a local engine + batcher,
    exactly one of the ``n_replicas`` clones) or a ``host:port``
    endpoint — a :class:`~torchbooster_tpu.serving.router.rpc.
    RemoteReplica` socket to a ``python -m torchbooster_tpu.serving.
    replica_server`` process pumping its own batcher. Routing,
    affinity, spill, health, and death-readmission semantics are
    identical either way (``tests/test_disagg.py::
    test_socket_replica_parity_tokens_and_assignments``); a dropped
    connection is replica
    death. Non-empty ``replicas`` overrides ``n_replicas``.

    ``audit`` sizes the routing-decision audit ring (``0`` disables
    it): one bounded record per choice — reason, affinity key, the
    per-candidate load picture — surfaced at ``GET /debug/router``
    and diffable via ``replay_diff --routing``. The ``health:``
    sub-block (:class:`RouterHealthConfig`) builds the per-replica
    health scorer; ``health_aware: true`` (needs ``health.enabled``)
    additionally lets spill/keyless scoring down-weight degraded
    replicas — off (the default) routing decisions are byte-identical
    whether or not the scorer observes.
    """

    n_replicas: int = 1                # 1 = plain single batcher
    replicas: list = dataclasses.field(
        default_factory=list)          # "inproc" | "host:port" specs
    policy: str = "affinity"           # round_robin | affinity
    affinity_pages: int = 2            # full pages hashed into the key
    spill_queue: int = 4               # hot-prefix spill threshold
    rebalance_queue: int = 0           # 0 = hot-spot rebalance off
    rebalance_after: int = 8           # sustained-imbalance steps
    directory: bool = True             # fleet-wide prefix directory
    audit: int = 256                   # decision audit ring (0 = off)
    health_aware: bool = False         # health-weighted spill scoring
    health: RouterHealthConfig = dataclasses.field(
        default_factory=RouterHealthConfig)  # replica health scorer

    def make_routing(self) -> Any:
        from torchbooster_tpu.serving.router import make_routing

        return make_routing(self.policy,
                            affinity_pages=self.affinity_pages,
                            spill_queue=self.spill_queue)

    def make(self, batchers: Any) -> Any:
        """Build the :class:`EngineFleet` over already-built replica
        batchers (normally ``ServingConfig.make``'s job)."""
        from torchbooster_tpu.serving.router import EngineFleet

        if self.health_aware and not self.health.enabled:
            raise ValueError(
                "router.health_aware: true needs router.health."
                "enabled: true (there is no scorer to consult)")
        return EngineFleet(batchers, routing=self.make_routing(),
                           rebalance_queue=self.rebalance_queue,
                           rebalance_after=self.rebalance_after,
                           directory=self.directory,
                           audit=self.audit,
                           health=self.health.make(),
                           health_aware=self.health_aware)


@dataclass
class ServingConfig(BaseConfig):
    """Serving-engine settings (torchbooster_tpu/serving): the paged
    KV cache's geometry and the sampling knobs of the continuous-
    batching decode loop. No reference analogue — the reference has no
    inference story; this is the serving half of the north star.

    Geometry sizes HBM and the per-step read: the pool holds
    ``(n_pages - 1) * page_size`` live tokens (page 0 is the reserved
    null page) and every decode step streams the whole pool once —
    size ``n_pages`` to expected total occupancy across ``max_slots``
    concurrent sequences, NOT to the worst case ``max_slots *
    seq_len`` (that is exactly the dense-cache behavior the pager
    exists to avoid; docs/performance.md "Serving" has the roofline).

    ``prefix_cache: true`` keeps retired requests' full prompt pages
    resident (refcounted, LRU-evicted under pool pressure) so a
    request sharing a prompt prefix — the shared-system-prompt
    traffic shape — maps those pages into its block table instead of
    re-prefilling them (token-identical to the cold path).
    ``prefill_chunk_pages`` sizes the prefill chunks the batcher
    interleaves between decode steps: one compiled chunk shape serves
    every prompt length, and decode latency stays bounded by one
    chunk while long prompts stream in.

    ``host_spill:`` (see :class:`HostSpillConfig`; needs
    ``prefix_cache``) adds the second page tier under the prefix
    cache: LRU eviction demotes registered prefix pages to a bounded
    host-DRAM pool instead of freeing them, and a later match
    promotes them back over PCIe through one compiled fixed-shape
    write — host-hit TTFT pays stream time, not recompute FLOPs.

    ``speculative: true`` switches decode to draft + batched-verify
    (serving/speculative.py): model-free prompt-lookup drafting
    proposes up to ``draft_len`` tokens per slot, one compiled verify
    step scores them all, and each slot emits ``accepted + 1`` tokens
    per pool read — greedy output stays token-identical to the cold
    engine; ``temperature > 0`` uses distribution-exact rejection
    sampling. ``ngram_min`` is the shortest history n-gram the
    drafter will match. ``draft_len`` must stay below ``page_size``
    (the engine validates loudly).

    ``spec_tree: true`` (greedy speculative engines only) upgrades
    the linear draft chain to a TREE of up to ``spec_tree_width``
    candidate branches verified in the SAME fused pass through
    ancestor-only visibility masks — when the stream's history is
    ambiguous (the same n-gram seen with different continuations)
    every plausible branch rides the verify step and the best
    accepted root-to-leaf path wins; unambiguous streams degenerate
    to the linear chain bit-for-bit.

    ``parallel_sampling: true`` enables copy-on-write parallel
    sampling — the OpenAI ``n``/``best_of`` surface: an n-way request
    prefills ONCE and forks into ``best_of`` branches sharing every
    full prompt page (one HBM read serves all branches), each branch
    sampling with its own ``fold_in(PRNGKey(seed), branch)`` key and
    accumulating token logprobs for ``best_of`` ranking. Mutually
    exclusive with ``speculative``. Off (the default) the engine is
    bit-for-bit the single-stream one.

    ``structured:`` (see :class:`StructuredConfig`) enables
    schema/regex-constrained decoding: requests carrying an OpenAI
    ``response_format`` decode under a per-slot token-DFA legality
    mask — compiled once per schema, threaded through the compiled
    steps as a trailing value operand (zero recompiles), composing
    with speculative decoding and parallel sampling.

    ``weights:`` (see :class:`WeightsConfig`) serves int8/int4
    quantized weights: one host-side pass at build time, dequant
    fused into every compiled matmul's operand read, so the decode
    roofline's weight stream drops to 1 (or 0.5) byte per element.

    ``adapters:`` (see :class:`AdaptersConfig`) enables batched
    multi-LoRA decode: concurrently-live adapters stacked on device
    lanes, gathered per slot by traced lane ids — many tenants on
    one page pool with zero recompiles across adapter churn.

    ``decode_backend`` chooses how the decode steps READ the pool.
    ``xla`` (default) is the live-page walk where the pool allows it
    and the whole-pool sweep where it does not: a pool of plain rows
    on one device is walked (ops/lane_walk.py — each decoding slot's
    table up to its own position, the pool read in place, a window
    layer's ring from the window's lower edge), an int8 pool,
    ``tp > 1`` and the speculative verify sweep every page
    (``kv_pages.sweep_attention``); a latent pool has its own kernel.
    ``pallas`` swaps the decode/verify READ for the older paged
    flash-decode kernel (ops/paged_attention.py), which relays a
    layer's pool by heads for its blocks. All are token-exact for
    greedy decode.

    ``tp > 1`` runs the engine TENSOR-PARALLEL over a committed mesh's
    ``tp`` (heads) axis (serving/tp.py; pass the mesh to
    :meth:`make`): Q/K/V/O projections and the KV page pool shard by
    heads, so per-chip KV bytes/step — the decode roofline's
    numerator — divide by ``tp``; block tables and all scheduling
    stay host-side and replicated. ``tp`` must divide ``n_kv_heads``
    (GQA shards by KV-head groups; ``n_heads`` under MHA) and must
    equal the mesh's ``tp`` axis size — both rejected loudly with the
    offending numbers, at YAML time here and again at engine build.
    The default ``tp: 1`` is the single-chip engine, bit-for-bit.
    """

    page_size: int = 64
    n_pages: int = 256
    max_slots: int = 8
    cache_dtype: str = ""              # "" (compute dtype) | "int8"
    temperature: float = 0.0           # 0 = greedy
    top_k: int = 0                     # 0 = off
    top_p: float = 0.0                 # 0 = off
    prefix_cache: bool = False         # share resident prompt prefixes
    prefill_chunk_pages: int = 4       # chunked-prefill granularity
    speculative: bool = False          # draft + batched-verify decode
    draft_len: int = 4                 # drafted tokens per verify step
    ngram_min: int = 2                 # shortest prompt-lookup n-gram
    spec_tree: bool = False            # tree-structured drafting (greedy)
    spec_tree_width: int = 2           # max branches off the draft root
    parallel_sampling: bool = False    # CoW fork n/best_of sampling
    decode_backend: str = "xla"        # "xla" walk / sweep | "pallas" kernel
    tp: int = 1                        # tensor-parallel head shards (mesh "tp" axis)
    frontend: FrontendConfig = dataclasses.field(
        default_factory=FrontendConfig)  # HTTP front door + scheduler
    router: RouterConfig = dataclasses.field(
        default_factory=RouterConfig)  # engine-fleet replica scale-out
    host_spill: HostSpillConfig = dataclasses.field(
        default_factory=HostSpillConfig)  # host-RAM page spill tier
    structured: StructuredConfig = dataclasses.field(
        default_factory=StructuredConfig)  # constrained decoding
    weights: WeightsConfig = dataclasses.field(
        default_factory=WeightsConfig)  # int8/int4 weight serving
    adapters: AdaptersConfig = dataclasses.field(
        default_factory=AdaptersConfig)  # batched multi-LoRA lanes
    disagg: DisaggConfig = dataclasses.field(
        default_factory=DisaggConfig)  # split prefill/decode pools

    def make(self, params: Any, model_cfg: Any,
             compute_dtype: Any = None,
             on_recompile: str = "warn",
             mesh: Any = None, tracer: Any = None) -> Any:
        """Build the engine + batcher for ``params``/``model_cfg`` (a
        :class:`~torchbooster_tpu.models.gpt.GPTConfig`, or another
        served model's config such as
        :class:`~torchbooster_tpu.models.lfm2.LFM2Config`,
        :class:`~torchbooster_tpu.models.mla_moe.MLAMoEConfig`,
        :class:`~torchbooster_tpu.models.afmoe.AfmoeConfig`,
        :class:`~torchbooster_tpu.models.qwen3_next.Qwen3NextConfig` or
        :class:`~torchbooster_tpu.models.bailing_hybrid.BailingHybridConfig`:
        the engine is chosen by its type, and the features a model lacks
        raise ``NotImplementedError`` naming the feature and the
        reason). Returns the
        :class:`~torchbooster_tpu.serving.ContinuousBatcher` — with
        the ``frontend:`` block's scheduler policy installed (the
        default is FCFS, byte-for-byte the policy-less batcher); its
        ``.engine`` exposes admit/step/retire for custom drivers, and
        ``self.frontend.make(batcher)`` wraps it in the HTTP server.
        ``on_recompile`` is the batcher's runtime-guard policy — pass
        your ``ObservabilityConfig.on_recompile`` so the YAML policy
        reaches the one region the docs advertise as guarded.
        ``mesh`` is the committed device mesh a ``tp > 1`` build
        shards over (must carry a ``tp`` axis of exactly that size —
        validated here with the offending numbers BEFORE any engine
        state is built, and again by the engine ctor). ``tracer`` is
        the request tracer to install (normally
        ``conf.observability.tracing.make()`` — the ONLY way the
        ``tracing:`` YAML block reaches a YAML-built batcher/fleet);
        a fleet shares it across every replica so ``/debug/trace``
        follows a request fleet-wide. The build is one
        ``serving_build`` span (a no-op while the registry is off):
        what its compiles cost is in ``compile_seconds{span=
        serving_build}``, docs/observability.md "Start-up"."""
        from torchbooster_tpu.observability.spans import span

        with span("serving_build"):
            return self._build(params, model_cfg, compute_dtype,
                               on_recompile, mesh, tracer)

    def _build(self, params: Any, model_cfg: Any, compute_dtype: Any,
               on_recompile: str, mesh: Any, tracer: Any) -> Any:
        import jax.numpy as jnp

        from torchbooster_tpu.serving import ContinuousBatcher, PagedEngine
        from torchbooster_tpu.serving.tp import check_tp
        from torchbooster_tpu.utils import enable_compile_cache

        enable_compile_cache()
        from torchbooster_tpu.models.gpt import GPTConfig

        if not isinstance(model_cfg, GPTConfig):
            # a model with its own layer stack (models/lfm2.py,
            # models/mla_moe.py, models/afmoe.py, models/qwen3_next.py,
            # models/bailing_hybrid.py):
            # the engine is chosen by the config's type and refuses the
            # features it lacks, each with the model's own reason;
            # these three never reach it
            from torchbooster_tpu.serving.engine import refuse_unserved

            refuse_unserved(model_cfg, {
                "weights (int8/int4)":
                    self.weights.dtype not in ("", "bf16"),
                "disagg (prefill_only)": self.disagg.enabled,
                "tp": self.tp > 1})
        # YAML-time rejection: a tp that does not divide the model's
        # KV-head count, exceeds/mismatches the mesh's tp axis, or
        # arrives without a committed mesh must fail HERE, with the
        # numbers, not as a shard_map shape error mid-build
        check_tp(self.tp, model_cfg, mesh)
        # ONE host-side quantization pass, BEFORE any engine is built
        # (and therefore before the engine's tp-major permute — the
        # permute moves qkernel/qscale columns like any other layout
        # fact); every replica shares the quantized tree
        params = self.weights.quantize(params)
        n_replicas = self.router.n_replicas
        if n_replicas < 1:
            raise ValueError(
                f"serving.router.n_replicas must be >= 1, got "
                f"{n_replicas}")
        if n_replicas > 1 and self.tp > 1:
            raise ValueError(
                f"serving.router.n_replicas={n_replicas} with "
                f"tp={self.tp} is not buildable from YAML: every "
                "replica would shard over the SAME tp mesh axis — "
                "build EngineFleet directly with per-replica meshes")

        def build_engine(*, prefill_only=False, n_pages=None,
                         max_slots=None, host_spill=None):
            return PagedEngine(
                params, model_cfg,
                page_size=self.page_size,
                n_pages=n_pages if n_pages else self.n_pages,
                max_slots=max_slots if max_slots else self.max_slots,
                cache_dtype=self.cache_dtype or None,
                compute_dtype=(jnp.bfloat16 if compute_dtype is None
                               else compute_dtype),
                temperature=self.temperature,
                top_k=self.top_k or None, top_p=self.top_p or None,
                prefix_cache=self.prefix_cache,
                prefill_chunk_pages=self.prefill_chunk_pages,
                speculative=self.speculative,
                draft_len=self.draft_len, ngram_min=self.ngram_min,
                spec_tree=self.spec_tree,
                tree_width=self.spec_tree_width,
                parallel_sampling=self.parallel_sampling,
                decode_backend=self.decode_backend,
                host_spill=(self.host_spill.enabled
                            if host_spill is None else host_spill),
                host_spill_mb=self.host_spill.budget_mb,
                prefill_only=prefill_only,
                structured=self.structured.enabled,
                lora_rank=self.adapters.rank,
                lora_max_live=(self.adapters.max_live
                               if self.adapters.rank > 0 else 0),
                tp=self.tp, mesh=mesh)

        # ONE policy object serves every replica AND the fleet-level
        # validate/backpressure surface (policies are stateless over
        # their class tables, so sharing is safe by construction)
        policy = self.frontend.make_policy()
        if self.disagg.enabled:
            from torchbooster_tpu.serving.disagg import DisaggPair

            if n_replicas > 1 or self.router.replicas:
                raise ValueError(
                    "serving.disagg.enabled with a multi-replica "
                    "router block: disaggregate AND replicate by "
                    "building the fleet directly over DisaggPairs")
            if not (self.prefix_cache and self.host_spill.enabled):
                raise ValueError(
                    "serving.disagg needs prefix_cache: true and "
                    "host_spill.enabled: true — the page stream "
                    "lands in the decode pool's host tier")
            if self.disagg.min_prefill_pages < 1:
                raise ValueError(
                    f"serving.disagg.min_prefill_pages must be >= 1, "
                    f"got {self.disagg.min_prefill_pages}")
            decode = ContinuousBatcher(build_engine(),
                                       on_recompile=on_recompile,
                                       policy=policy, tracer=tracer)
            prefill = build_engine(
                prefill_only=True,
                n_pages=self.disagg.prefill_n_pages or None,
                max_slots=self.disagg.prefill_max_slots or None,
                host_spill=False)
            return DisaggPair(
                prefill, decode,
                min_prefill_pages=self.disagg.min_prefill_pages)
        if self.router.replicas:
            from torchbooster_tpu.serving.router.rpc import (
                RemoteReplica)

            members = []
            for i, spec in enumerate(self.router.replicas):
                spec = str(spec).strip()
                if spec == "inproc":
                    members.append(ContinuousBatcher(
                        build_engine(), on_recompile=on_recompile,
                        policy=policy, tracer=tracer))
                elif ":" in spec:
                    members.append(RemoteReplica(spec, replica_id=i))
                else:
                    raise ValueError(
                        f"serving.router.replicas[{i}]={spec!r}: "
                        "expected 'inproc' or a 'host:port' endpoint")
            return self.router.make(members)
        if n_replicas == 1:
            return ContinuousBatcher(build_engine(),
                                     on_recompile=on_recompile,
                                     policy=policy, tracer=tracer)
        # the fleet: N identical replicas sharing params, the policy
        # table, and ONE tracer ring (so /debug/trace follows a
        # request across replicas by its id)
        if tracer is None:
            from torchbooster_tpu.observability.tracing import (
                RequestTracer)

            tracer = RequestTracer()
        batchers = [ContinuousBatcher(build_engine(),
                                      on_recompile=on_recompile,
                                      policy=policy, tracer=tracer)
                    for _ in range(n_replicas)]
        return self.router.make(batchers)


@dataclass
class LoadgenConfig(BaseConfig):
    """Workload source for the capture/replay harness
    (torchbooster_tpu/serving/loadgen). No reference analogue — this
    is how serving perf claims get measured under realistic load
    instead of ad-hoc Poisson loops.

    ``source`` is either a synthetic generator name (``poisson`` |
    ``bursty`` | ``diurnal`` | ``sharegpt`` | ``longprompt_burst`` —
    the last adds ``long_frac`` × ``n_requests`` EXTRA long prompts
    in ``long_prompt_len``, bursting once per workload period on top
    of byte-identical Poisson base traffic: the disaggregation
    stressor) or a path to a captured
    workload JSONL (``serving.frontend.capture_path`` writes one; a
    path is recognized by its ``.jsonl``/``.json`` suffix or by
    existing on disk). Both produce the SAME versioned format, so
    synthetic and captured traffic flow through one replay driver.

    ``speed`` is the time-compression ×-factor replays default to:
    ``make()`` records it as the workload's ``meta["speed"]``, which
    ``replay_inprocess``/``replay_http`` use whenever their own
    ``speed`` argument is omitted (arrival offsets divide by it;
    relative order is preserved).
    ``classes`` is a ``"name:weight,..."`` priority mix for the
    synthetic kinds (class SLO targets come from the frontend's own
    ``classes`` table); ``cancel_frac`` of synthetic requests get a
    recorded client disconnect at a random token offset, so replay
    exercises the cancel/abort paths. ``prompt_len`` /
    ``max_new_tokens`` are inclusive ``(lo, hi)`` ranges. ``n_frac``
    gives that fraction of synthetic requests parallel-sampling
    fan-out (``n = best_of`` drawn in ``[2, n_max]``), so replays
    carry OpenAI ``n``/``best_of`` traffic through the harness —
    serve them against a ``serving.parallel_sampling: true`` engine.
    ``structured_frac`` gives that fraction of synthetic requests an
    OpenAI ``response_format`` drawn from the built-in schema
    library (format v3) — serve them against a
    ``serving.structured.enabled: true`` engine; at ``0.0`` (the
    default) the workload is byte-identical to pre-knob output.
    ``tenants > 0`` (with ``prefix_pages >= 1``) prepends each
    synthetic request with one of ``tenants`` fixed page-aligned
    system prompts of ``prefix_pages * prefix_page_size`` tokens —
    the many-tenant shared-prefix shape that overflows the HBM
    prefix cache and exercises the host spill tier (match
    ``prefix_page_size`` to ``serving.page_size``); ``tenants: 0``
    traffic is byte-identical to pre-knob workloads.

    ``make()`` returns the
    :class:`~torchbooster_tpu.serving.loadgen.workload.Workload`;
    drive it with ``replay_inprocess(batcher, wl, speed=...)`` or
    ``replay_http(port, wl, speed=...)``. docs/observability.md has
    the capture-and-replay walkthrough;
    ``tests/test_loadgen.py::test_http_capture_replay_round_trip_exact``
    proves the round trip.
    """

    source: str = "poisson"            # kind | capture-file path
    n_requests: int = 32
    rate: float = 8.0                  # offered req/s (synthetic)
    speed: float = 1.0                 # replay time-compression x
    seed: int = 0
    vocab: int = 50257
    prompt_len: tuple(int, int) = (16, 64)
    max_new_tokens: tuple(int, int) = (8, 32)
    classes: str = ""                  # "name:weight,..." mix
    cancel_frac: float = 0.0           # recorded client disconnects
    n_frac: float = 0.0                # fraction with n/best_of > 1
    n_max: int = 4                     # largest synthetic n
    structured_frac: float = 0.0       # fraction with response_format
    tenants: int = 0                   # 0 = no shared tenant prefixes
    prefix_pages: int = 0              # tenant system-prompt pages
    prefix_page_size: int = 64         # page alignment of the prefix
    long_prompt_len: tuple(int, int) = (256, 512)  # longprompt_burst
    long_frac: float = 0.25            # extra long requests / n_requests

    def make(self) -> Any:
        from torchbooster_tpu.serving.loadgen.workload import (
            SYNTHETIC_KINDS, Workload, synthesize)

        if self.speed <= 0:
            raise ValueError(
                f"loadgen.speed must be > 0, got {self.speed}")
        src = self.source.strip()
        if src.endswith((".jsonl", ".json")) or Path(src).exists():
            wl = Workload.load(src)
        elif src not in SYNTHETIC_KINDS:
            raise ValueError(
                f"loadgen.source={src!r}: expected a synthetic kind "
                f"{SYNTHETIC_KINDS} or a capture file path (got "
                "neither — a typo'd path would silently synthesize "
                "the wrong traffic)")
        else:
            wl = synthesize(
                src, n_requests=self.n_requests, rate=self.rate,
                seed=self.seed, vocab=self.vocab,
                prompt_len=tuple(self.prompt_len),
                max_new_tokens=tuple(self.max_new_tokens),
                classes=self.classes, cancel_frac=self.cancel_frac,
                n_frac=self.n_frac, n_max=self.n_max,
                structured_frac=self.structured_frac,
                tenants=self.tenants,
                prefix_pages=self.prefix_pages,
                page_size=self.prefix_page_size,
                long_prompt_len=tuple(self.long_prompt_len),
                long_frac=self.long_frac)
        # the block's replay default: drivers called without an
        # explicit speed= read it back from the workload, so the
        # YAML knob actually governs the replay (meta never enters
        # the content fingerprint)
        wl.meta["speed"] = float(self.speed)
        return wl


@dataclass
class CommsConfig(BaseConfig):
    """Gradient-communication schedule (torchbooster_tpu/comms): the
    ZeRO stage, the wire format of the data-parallel gradient sync,
    and whether the sync overlaps backward. No reference analogue —
    the reference's DDP all-reduce was NCCL's business; here the
    bytes are a config line.

    YAML block::

        comms:
          stage: 0           # ZeRO ladder: 0 | 1 | 2 | 3
          wire: fp32         # fp32 | bf16 | int8 (grad wire format)
          overlap: false     # stage>=2: reduce-scatter inside backward
          bucket_mb: 4.0     # comm-bucket size for the overlapped sync
          bucket_size: 512   # int8 quantization bucket (fp32 scale each)

    ``stage: 0`` all-reduces gradients (explicit, the A/B control);
    ``stage: 1`` (ZeRO-1) shards the optimizer update; ``stage: 2``
    (ZeRO-2) reduce-scatters gradients bucket-by-bucket — *during*
    backward with ``overlap: true``; ``stage: 3`` (ZeRO-3) also
    shards params at rest and all-gathers them just in time in
    forward — inherently overlapped (the gather hooks' backward IS
    the reduce-scatter), so ``overlap`` normalizes to true at stage
    3; there is no serialized variant. ``wire: bf16``/``int8`` compress the grad bytes 2×/~4×
    (int8 carries error-feedback residuals in ``TrainState.comms``).
    Bad combinations fail loudly naming the offending keys
    (``overlap`` needs ``stage`` >= 2; stages >= 2 need an explicit
    ``wire``). Omitting the whole block keeps XLA's own implicit
    fp32 psum, bit-identical to before this subsystem existed.

    Legacy keys ``mode:`` (``implicit | fp32 | bf16 | int8``) and
    ``zero1:`` still load — they shim onto ``{stage: 0|1, wire:
    mode}`` with a deprecation note — but cannot be mixed with the
    schedule keys in one block. See docs/parallelism.md
    "Gradient communication" for the ladder matrix.
    """

    stage: int = -1                    # 0 | 1 | 2 | 3 (-1: unset/legacy)
    wire: str = ""                     # fp32 | bf16 | int8 ("": unset)
    overlap: bool = False              # stage>=2 only
    bucket_mb: float = 4.0             # comm-bucket target (MB, fp32)
    mode: str = "implicit"             # legacy: implicit|fp32|bf16|int8
    zero1: bool = False                # legacy: stage-1 switch
    bucket_size: int = 512

    def make(self, env: Any = None, mesh: Any = None) -> Any:
        """Build the :class:`~torchbooster_tpu.comms.CommsSchedule`
        for ``mesh`` (or the ``env``'s cached mesh): pass it to
        ``utils.make_step(comms=...)`` and build states with
        ``.create_state(params, tx)``."""
        import logging

        from torchbooster_tpu import distributed as dist
        from torchbooster_tpu.comms import make_schedule

        if mesh is None:
            mesh = dist.get_mesh(env)
        selector_keys = {}
        if self.stage != -1:
            selector_keys["stage"] = self.stage
        if self.wire:
            selector_keys["wire"] = self.wire
        if self.overlap:
            selector_keys["overlap"] = self.overlap
        tuning_keys = {}
        if self.bucket_mb != 4.0:
            tuning_keys["bucket_mb"] = self.bucket_mb
        new_keys = {**selector_keys, **tuning_keys}
        legacy_keys = {}
        if self.mode != "implicit":
            legacy_keys["mode"] = self.mode
        if self.zero1:
            legacy_keys["zero1"] = self.zero1
        if new_keys and legacy_keys:
            raise ValueError(
                f"comms: block mixes legacy keys "
                f"{sorted(legacy_keys)} with schedule keys "
                f"{sorted(new_keys)} — express the whole plan as "
                f"stage/wire/overlap (mode: {self.mode!r} zero1: "
                f"{self.zero1} is comms: {{stage: "
                f"{1 if self.zero1 else 0}, wire: {self.mode!r}}})")
        if tuning_keys and not selector_keys:
            raise ValueError(
                f"comms: {{bucket_mb: {self.bucket_mb}}} only shapes "
                f"the stage>=2 comm buckets — on its own it would "
                f"silently replace the implicit psum with an explicit "
                f"stage-0 sync. Add stage: (and wire:) to select the "
                f"schedule, or drop bucket_mb.")
        if new_keys:
            return make_schedule(mesh,
                                 stage=max(0, self.stage),
                                 wire=self.wire or "fp32",
                                 overlap=self.overlap,
                                 bucket_mb=self.bucket_mb,
                                 bucket_size=self.bucket_size)
        # legacy shim: mode/zero1 map onto stages 0/1 bit-for-bit
        # (implicit grads + sharded update stays the implicit-wire
        # stage-1 schedule it always silently was — now it says so)
        stage = 1 if self.zero1 else 0
        if legacy_keys:
            logging.warning(
                "comms: mode/zero1 are deprecated — this block is the "
                "schedule comms: {stage: %d, wire: %s}; the schedule "
                "keys also unlock stage 2/3 and overlap",
                stage, self.mode)
        from torchbooster_tpu.comms import (as_schedule,
                                            make_grad_comms)

        return as_schedule(make_grad_comms(
            mesh, mode=self.mode, zero1=self.zero1,
            bucket_size=self.bucket_size))


@dataclass
class TracingConfig(BaseConfig):
    """Request-scoped tracing switch (torchbooster_tpu/observability/
    tracing.py). Nested under ``observability:`` as its ``tracing:``
    sub-block.

    YAML block::

        observability:
          tracing:
            enabled: false             # per-request lifecycle events
            ring_size: 8192            # bounded event ring (oldest drop)
            trace_path: ""             # '' = no JSONL trace file on close
            chrome_path: ""            # '' = no Chrome trace file on close

    ``enabled: false`` (the default) leaves the serving batcher's
    metric values and compiled artifacts bit-for-bit unchanged — the
    tracer is one branch per emit site and stamps its own monotonic
    clock. ``make()`` builds the
    :class:`~torchbooster_tpu.observability.tracing.RequestTracer`
    (pass it to ``ContinuousBatcher(tracer=...)``); ``export(tracer)``
    writes ``trace_path`` (JSONL) / ``chrome_path`` (Chrome
    trace-event JSON, opens directly in Perfetto) when set."""

    enabled: bool = False
    ring_size: int = 8192
    trace_path: str = ""               # JSONL event dump on export()
    chrome_path: str = ""              # Chrome trace dump on export()

    def make(self) -> Any:
        from torchbooster_tpu.observability.tracing import RequestTracer

        return RequestTracer(enabled=self.enabled,
                             ring_size=self.ring_size)

    def export(self, tracer: Any) -> list:
        """Write the configured trace file(s) from ``tracer``'s ring;
        returns the paths written (empty when both paths are '')."""
        written = []
        if self.trace_path:
            written.append(tracer.write_jsonl(self.trace_path))
        if self.chrome_path:
            written.append(tracer.write_chrome(self.chrome_path))
        return written


@dataclass
class SLOBurnConfig(BaseConfig):
    """SLO burn-rate alerting switch (torchbooster_tpu/observability/
    slo.py). Nested under ``observability:`` as its ``slo:``
    sub-block.

    YAML block::

        observability:
          slo:
            enabled: false             # burn-rate engine on the export tick
            target: 0.99               # deadline-hit-rate objective
            fast_window_s: 60.0        # detection window
            slow_window_s: 600.0       # blip-veto window
            fire_burn: 2.0             # fire when BOTH windows >= this
            resolve_burn: 1.0          # resolve when fast window < this
            goodput_floor_tok_s: 0.0   # 0 = no goodput-floor alert

    ``make()`` builds the
    :class:`~torchbooster_tpu.observability.slo.SLOBurnEngine` (or
    ``None`` when disabled); ``ObservabilityConfig.make()`` hands it
    to the cadence exporter so burn gauges refresh on every export
    tick and alert transitions land in the JSONL log."""

    enabled: bool = False
    target: float = 0.99
    fast_window_s: float = 60.0
    slow_window_s: float = 600.0
    fire_burn: float = 2.0
    resolve_burn: float = 1.0
    goodput_floor_tok_s: float = 0.0   # 0 disables the goodput alert

    def make(self, sink: Any = None) -> Any:
        if not self.enabled:
            return None
        from torchbooster_tpu.observability.slo import SLOBurnEngine

        return SLOBurnEngine(
            target=self.target,
            fast_window_s=self.fast_window_s,
            slow_window_s=self.slow_window_s,
            fire_burn=self.fire_burn,
            resolve_burn=self.resolve_burn,
            goodput_floor_tok_s=self.goodput_floor_tok_s,
            sink=sink)


@dataclass
class ObservabilityConfig(BaseConfig):
    """Telemetry switch + exporter wiring (torchbooster_tpu/
    observability). No reference analogue — the reference's profiling
    story never worked (SURVEY §5.1); this is the production
    metrics/tracing/export layer.

    YAML block::

        observability:
          enabled: true
          jsonl_path: logs/telemetry.jsonl     # '' disables the event log
          prom_path: logs/metrics.prom         # '' disables Prometheus
          cadence_s: 10                        # export tick
          on_recompile: warn                   # ignore | warn | raise
          tracing:                             # request-scoped tracing
            enabled: false
          slo:                                 # burn-rate alerting
            enabled: false

    ``make()`` returns an :class:`~torchbooster_tpu.observability.
    Observability` session handle (context-manager: flushes exporters
    on exit). With ``enabled: false`` the handle is inert and every
    instrumented call site in the stack stays a single branch.
    ``tracing`` is the per-request trace sub-block
    (:class:`TracingConfig` — build its tracer with
    ``conf.observability.tracing.make()`` and hand it to the serving
    batcher); ``slo`` is the burn-rate alerting sub-block
    (:class:`SLOBurnConfig` — its engine rides the exporter
    cadence)."""

    enabled: bool = False
    jsonl_path: str = ""
    prom_path: str = ""
    cadence_s: float = 10.0
    on_recompile: str = "warn"         # ignore | warn | raise
    tracing: TracingConfig = dataclasses.field(
        default_factory=TracingConfig)  # request-scoped tracing
    slo: SLOBurnConfig = dataclasses.field(
        default_factory=SLOBurnConfig)  # burn-rate alerting

    def make(self) -> Any:
        from torchbooster_tpu import observability as obs

        from torchbooster_tpu.observability.recompile import POLICIES

        if self.on_recompile not in POLICIES:
            raise ValueError(
                f"on_recompile={self.on_recompile!r}: expected one "
                f"of {POLICIES}")
        if not self.enabled:
            # authoritative: `enabled: false` turns the process
            # default OFF even if an earlier session enabled it —
            # otherwise instrumentation keeps queueing with no
            # exporter left to drain it
            return obs.Observability(obs.set_enabled(False),
                                     on_recompile=self.on_recompile)
        return obs.enable(jsonl_path=self.jsonl_path or None,
                          prom_path=self.prom_path or None,
                          cadence_s=self.cadence_s,
                          on_recompile=self.on_recompile,
                          slo=self.slo.make())


@dataclass
class DatasetConfig(BaseConfig):
    """Dataset resolution (ref config.py:528-617).

    Reference chain: torchvision → torchtext → HuggingFace → fatal.
    TPU-native chain: builtin registry (synthetic + record-store readers,
    network-free) → local record-store directory under ``root/<split>`` →
    HuggingFace ``datasets`` (if importable and reachable) → logging.fatal
    + exit(1) (ref config.py:616-617)."""

    name: str = "mnist"
    root: str = "dataset"
    task: str = ""                     # HF config name (ref task field)
    n_examples: int = 0                # synthetic-family size override (0 = default)

    def make(
        self,
        split: Any,
        download: bool = True,
        distributed: bool = False,
        acceptance_fn: Callable | None = None,
        **kwargs: Any,
    ) -> Any:
        from torchbooster_tpu.data import resolve_dataset

        return resolve_dataset(
            self, split, download=download, distributed=distributed,
            acceptance_fn=acceptance_fn, **kwargs)


__all__ = [
    "BaseConfig",
    "CommsConfig",
    "DatasetConfig",
    "EnvConfig",
    "EnvironementConfig",
    "HostSpillConfig",
    "HyperParameterConfig",
    "LoadgenConfig",
    "LoaderConfig",
    "ObservabilityConfig",
    "OptimizerConfig",
    "RouterConfig",
    "RouterHealthConfig",
    "SLOBurnConfig",
    "SchedulerConfig",
    "ServingConfig",
    "TracingConfig",
    "do_include",
    "parse_sweep",
    "read_lines",
    "resolve_types",
]
