"""ElasticTrainer: sharded train step with elastic gradient accumulation.

Parity target: the reference's `ElasticTrainer`
(`dlrover/trainer/torch/elastic/trainer.py:181-336` there) keeps the
*global* batch size fixed as the world grows/shrinks by re-deriving the
gradient-accumulation count and stepping the optimizer only at sync
boundaries. TPU-native version:

- the "world" is the mesh; accumulation count =
  ``global_batch // (micro_batch * data_parallel_size)`` re-derived on each
  re-mesh (`ElasticTrainer.accum_steps`);
- accumulation is a `lax.scan` over microbatches *inside one jitted step*
  (no eager loop, no grad hooks) — gradients live in one sharded f32
  accumulator, XLA overlaps the dp/fsdp reduce with backward compute;
- optimizer is optax (adamw + cosine), optimizer state sharded like the
  params (ZeRO by construction — optimizer state inherits the fsdp specs);
- the step reports to the master's SpeedMonitor via the worker context
  (`report_step`), which feeds goodput accounting and autoscaling exactly
  like the reference's `report_global_step` path.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Any, Callable, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from dlrover_tpu.common import flags
from dlrover_tpu.common.log import logger
from dlrover_tpu.common.world import WorldDescriptor
from dlrover_tpu.lint import retrace_guard
from dlrover_tpu.observability import trace
from dlrover_tpu.observability.digest import StepTimeDigest
from dlrover_tpu.ops import attention, fused_ce, hier_collectives
from dlrover_tpu.parallel.mesh import MeshConfig
from dlrover_tpu.parallel.sharding import batch_spec
from dlrover_tpu.train import live_reshard, warm_compile, zero1

PyTree = Any


@dataclasses.dataclass(frozen=True)
class _Avatar:
    """Mesh-independent stand-in for one state/batch leaf: enough to
    rebuild a ``jax.ShapeDtypeStruct`` (with sharding) against any
    target mesh. A plain object on purpose — pytree LEAF, so avatar
    trees keep the state's treedef."""

    shape: Tuple[int, ...]
    dtype: Any
    spec: Any  # PartitionSpec (state leaves) | None (batch leaves)

    @property
    def size(self) -> int:
        return int(np.prod(self.shape)) if self.shape else 1


def _avatar_of(leaf) -> _Avatar:
    sharding = getattr(leaf, "sharding", None)
    spec = getattr(sharding, "spec", None)
    if spec is None:
        spec = P()  # single-device / uncommitted: replicated on retarget
    return _Avatar(tuple(leaf.shape), np.dtype(leaf.dtype), spec)


@dataclasses.dataclass
class TrainConfig:
    global_batch_size: int = 32
    micro_batch_size: int = 4          # per data-parallel shard
    learning_rate: float = 3e-4
    warmup_steps: int = 100
    total_steps: int = 10000
    weight_decay: float = 0.1
    grad_clip: float = 1.0
    b1: float = 0.9
    b2: float = 0.95
    # ZeRO-1 weight-update sharding across dp (train/zero1.py):
    # reduce-scatter grads, update dp-sharded adam moments, all-gather
    # the params. No-op on meshes without a dp axis > 1.
    zero1: bool = False
    # Hierarchical DCN-aware gradient reduction on multislice meshes
    # (ops/hier_collectives.py): ICI reduce-scatter within each slice,
    # DCN exchange of only the slice-local 1/dp_in shard, ICI
    # all-gather. The flat path is the fallback. No-op on single-slice
    # meshes (the trainer's n_slices).
    hier_collectives: bool = True
    # Latency-hiding schedule of the hierarchical reduction
    # (ops/hier_collectives.py overlap_value_and_grad): bucket the
    # grads, run the ICI leg eagerly and carry each microbatch's DCN
    # exchange through the accumulation scan behind the NEXT
    # microbatch's backward. Same reduction, pipelined. Only effective
    # where hier itself applies; with accum == 1 there is no backward
    # to hide behind and the schedule degenerates to hier's.
    overlap_collectives: bool = True


def make_optimizer(tc: TrainConfig) -> optax.GradientTransformation:
    if tc.warmup_steps > 0:
        sched = optax.warmup_cosine_decay_schedule(
            0.0, tc.learning_rate, tc.warmup_steps,
            max(tc.total_steps, tc.warmup_steps + 1), tc.learning_rate * 0.1,
        )
    else:
        sched = optax.cosine_decay_schedule(
            tc.learning_rate, max(tc.total_steps, 1), 0.1
        )
    return optax.chain(
        optax.clip_by_global_norm(tc.grad_clip),
        optax.adamw(sched, b1=tc.b1, b2=tc.b2, weight_decay=tc.weight_decay),
    )


class ElasticTrainer:
    """Builds and owns the jitted, sharded train step."""

    def __init__(
        self,
        loss_fn: Optional[Callable[[PyTree, jnp.ndarray], jnp.ndarray]],
        p_specs: PyTree,
        mesh: Mesh,
        mesh_config: MeshConfig,
        train_config: TrainConfig,
        worker_ctx=None,
        loss_factory: Optional[Callable[[Optional[Mesh]], Callable]] = None,
        n_slices: int = 1,
    ):
        """``loss_fn`` may close over the live mesh (sharding
        constraints); that pins the step to one mesh forever. Passing
        ``loss_factory`` (mesh → loss_fn) instead lets the trainer
        re-derive the loss for any mesh — which is what makes
        cross-world AOT compilation (``lower_step`` for a world that is
        not live) and true in-process ``remesh()`` possible. With only
        ``loss_fn``, speculative neighbor compilation stays off.

        ``n_slices``: distinct TPU slices the mesh spans (the agent
        injects it as ``DLROVER_TPU_NUM_SLICES`` — ``WorkerEnv.
        num_slices``). >1 arms the hierarchical DCN-aware gradient
        reduction (ops/hier_collectives.py) and the per-link comm
        inventory; 1 (the default) is byte-identical to before."""
        self.loss_factory = loss_factory
        if loss_fn is None:
            if loss_factory is None:
                raise ValueError("need loss_fn or loss_factory")
            loss_fn = loss_factory(mesh)
        self.loss_fn = loss_fn
        self.p_specs = p_specs
        self.mesh = mesh
        self.mesh_config = mesh_config
        self.tc = train_config
        self.n_slices = max(1, int(n_slices))
        self.optimizer = make_optimizer(train_config)
        self.worker_ctx = worker_ctx
        self._step_fn = None
        self._eval_fn = None
        self._host_step = 0
        self._applied_config_version = 0
        # warm-compile layer (train/warm_compile.py): AOT executable
        # cache + the speculative neighbor-compile thread. Avatars are
        # captured from the first state/batch seen so the step can be
        # lowered for meshes that are not live.
        self.warm = warm_compile.WarmCompiler()
        self._state_avatar: Optional[PyTree] = None
        self._batch_avatar: Optional[PyTree] = None
        self._params_avatar: Optional[PyTree] = None
        # optional semantic hints for the shardcheck IR rules (SC003
        # needs seq_len and vocab to recognize a dense-logits tensor);
        # entry scripts that know the model set this, e.g.
        # trainer.shardcheck_hints = {"seq_len": s, "vocab": v}
        self.shardcheck_hints: dict = {}
        # open resize event (remesh() stamps the transfer half; the
        # first post-resize step build stamps the compile half and
        # records it to live_reshard.resize_ledger)
        self._pending_resize: Optional[dict] = None
        # planner-directed speculation target (set_speculation_hint):
        # the exact WorldDescriptor the master's goodput planner
        # intends next — compiled FIRST by the speculative thread
        self._speculation_hint: Optional[WorldDescriptor] = None
        # silent-recompile guard (lint/retrace_guard.py), opt-in via
        # DLROVER_TPU_RETRACE_GUARD: raises in place when the step (or
        # any jitted fn) recompiles an already-seen signature or drifts
        # through too many distinct ones
        self._retrace_guard = retrace_guard.maybe_install()
        # per-rank step-time digest (observability/digest.py): every
        # step folds its host wall seconds; the throttled report_step
        # drains one window to the master's straggler detector and
        # lost-time attribution
        self.step_digest = StepTimeDigest()
        # the stepping thread's account of every interval between two
        # entries to step() (observability/trace.py StepAccount): one
        # row a step into the spine, and the digest's sample
        self._account = trace.StepAccount()
        trace.install_gc_hook()
        self._maybe_serve_comm_metrics()

    def _maybe_serve_comm_metrics(self):
        """Worker-side /metrics for the per-collective ledger
        (profiler/comm.py), opted in with
        ``DLROVER_TPU_COMM_METRICS_PORT`` (0 = ephemeral port)."""
        port_num = flags.COMM_METRICS_PORT.get()
        if port_num is None:
            return  # unset (or non-numeric: flags warned) = disabled
        from dlrover_tpu.profiler.comm import start_metrics_server

        try:
            _, bound = start_metrics_server(port_num)
            from dlrover_tpu.common.log import logger as _logger

            _logger.info("comm metrics on 127.0.0.1:%d/metrics", bound)
        except OSError:
            pass  # port taken (another trainer in-process)

    # ---- zero-1 weight-update sharding (train/zero1.py) ----------------
    def _zero1_mode(self, mesh: Mesh) -> str:
        """``"off"`` | ``"scatter"`` | ``"gspmd"`` — how the weight
        update shards over dp on ``mesh``."""
        return zero1.mode_for(mesh, self.tc, self.loss_factory is not None)

    def _slices_for(self, mesh: Mesh) -> int:
        """Slice count of ``mesh``: the live mesh carries the trainer's
        ``n_slices``; a warm-compile TARGET mesh (speculative neighbor,
        cross-world lowering) derives it from the invariant that slices
        are atomic resize units — devices per slice stay constant, so a
        neighbor world's slice count is ``size / per_slice``. Worlds
        that don't tile into whole slices are treated single-slice
        (they could only run flat anyway)."""
        return self._slices_for_size(mesh.size)

    def _slices_for_size(self, size: int) -> int:
        if self.n_slices <= 1:
            return 1
        if size == self.mesh.size:
            return self.n_slices
        per = self.mesh.size // self.n_slices
        if per > 0 and size % per == 0:
            return max(1, size // per)
        return 1

    def _hier_mode(self, mesh: Mesh) -> str:
        """``"flat"`` | ``"hier"`` | ``"overlap"`` — how the dp
        gradient reduction is scheduled over the slice topology
        (ops/hier_collectives.py); ``overlap`` is the hierarchy plus
        the latency-hiding bucketed DCN pipeline."""
        return hier_collectives.mode_for(
            mesh, self._slices_for(mesh), self.tc,
            self.loss_factory is not None,
            zero1_mode=self._zero1_mode(mesh),
        )

    def _state_avatar_for(self, mesh: Mesh) -> Optional[PyTree]:
        """State avatars with the optimizer-state specs RE-DERIVED for
        ``mesh``. Zero-1 shards each moment along whatever dim divides
        on the *current* dp size — a resized dp (or a zero-1 on/off
        flip at a resize boundary) changes the answer — so every
        cross-mesh consumer (AOT lowering, live-reshard transfer
        targets, checkpoint restore placement) re-derives here instead
        of reusing the captured specs verbatim. Leaves outside ``opt``
        never carry dp (the zero1.py invariant: dp only enters a state
        spec through that module) and pass through untouched."""
        if self._state_avatar is None:
            return None
        mode = self._zero1_mode(mesh)
        axis_sizes = dict(mesh.shape)

        def retarget(av):
            if not av.shape:
                return av
            has_dp = zero1.spec_has_dp(av.spec)
            if mode == "off" and not has_dp:
                # nothing to do — and strip_spec's trailing-None
                # normalization must not churn an untouched spec
                # (P(None,) and P() place identically but compare
                # unequal as NamedShardings)
                return av
            base = zero1.strip_spec(av.spec) if has_dp else av.spec
            z = (
                zero1.partition_spec(base, av.shape, axis_sizes)
                if mode != "off" else None
            )
            spec = z if z is not None else base
            if spec == av.spec:
                return av
            return dataclasses.replace(av, spec=spec)

        out = dict(self._state_avatar)
        if "opt" in out:
            out["opt"] = jax.tree.map(retarget, out["opt"])
        return out

    def state_targets(self, mesh: Optional[Mesh] = None) -> PyTree:
        """``ShapeDtypeStruct`` (with sharding) restore/transfer targets
        for ``mesh`` (default: live): state shapes from the avatars,
        optimizer-state specs re-derived for the target world (zero-1
        aware). The one tree checkpoint restore should place
        against — placing by raw captured
        avatars instead would pin a resized world to the OLD dp's
        moment layout."""
        mesh = mesh if mesh is not None else self.mesh
        avatars = self._state_avatar_for(mesh)
        if avatars is None:
            raise RuntimeError(
                "state_targets needs avatars: run one step() or call "
                "record_avatars(state, batch) first"
            )
        # no world= check here: the only descriptor available derives
        # from this same mesh (a self-comparison proves nothing);
        # remesh() passes a config-derived one where it is meaningful
        return live_reshard.state_targets(avatars, mesh)

    # ---- elastic global-batch math (reference trainer.py:307-327) ------
    @property
    def accum_steps(self) -> int:
        return self._accum_for(self.mesh, self.mesh_config)

    def _accum_for(self, mesh: Mesh, mesh_config: MeshConfig) -> int:
        """Accumulation count keeping the global batch fixed on any
        (mesh, config) — the live pair or a warm-compile target."""
        dp = mesh_config.resolve(mesh.size).data_parallel_size
        denom = self.tc.micro_batch_size * dp
        if self.tc.global_batch_size % denom:
            raise ValueError(
                f"global_batch={self.tc.global_batch_size} not divisible by "
                f"micro_batch*dp={denom}"
            )
        return self.tc.global_batch_size // denom

    @property
    def batch_sharding(self):
        """The NamedSharding the jitted step expects for its batch —
        the single source of truth input pipelines (prefetch) should
        place against."""
        return NamedSharding(self.mesh, P(None, *batch_spec()))

    @property
    def step_batch_shape(self) -> Tuple[int, int]:
        """(accum_steps, global_batch_per_accum) — how callers should shape
        the token batch fed to `step`."""
        dp = self.mesh_config.resolve(self.mesh.size).data_parallel_size
        return self.accum_steps, self.tc.micro_batch_size * dp

    def init_state(self, params: PyTree) -> dict:
        # EAGER init so adam's mu/nu are born with the params' shardings:
        # eager zeros_like follows its input's sharding exactly
        # (optimizer state is ZeRO-sharded for free whenever params carry
        # fsdp specs), whereas jit(opt.init) leaves the OUTPUT shardings
        # to XLA, which has been seen to choose SingleDeviceSharding for
        # some leaves — poisoning every later restore that places leaves
        # by this target's sharding (resized-world restore path).
        self._params_avatar = jax.tree.map(_avatar_of, params)
        self._record_data_parallel_comm(params)
        opt_state = self.optimizer.init(params)
        # scalars born mesh-replicated, not on the default device: a
        # checkpoint restore places leaves by the target's sharding, and
        # a single-device-committed scalar (adam's count, step, lr_scale)
        # next to mesh-wide params makes the jitted step reject the
        # state (resized-world restore path)
        repl = NamedSharding(self.mesh, P())
        opt_state = jax.tree.map(
            lambda l: jax.device_put(l, repl) if getattr(l, "ndim", None)
            == 0 else l,
            opt_state,
        )
        if self._zero1_mode(self.mesh) != "off":
            # ZeRO-1 (train/zero1.py): re-place every non-scalar moment
            # dp-sharded along its leading divisible dim. The step's
            # update runs on (and returns) exactly this layout, and the
            # avatars captured from this state carry it into the AOT
            # signatures, live-reshard targets and restore placements.
            axis_sizes = dict(self.mesh.shape)

            def _shard_moment(l):
                if getattr(l, "ndim", 0) == 0:
                    return l
                spec = getattr(getattr(l, "sharding", None), "spec", None)
                z = zero1.partition_spec(
                    spec if spec is not None else P(), l.shape, axis_sizes
                )
                if z is None:
                    return l  # non-divisible leaf: replicated fallback
                return jax.device_put(l, NamedSharding(self.mesh, z))

            opt_state = jax.tree.map(_shard_moment, opt_state)
        return {
            "params": params,
            "opt": opt_state,
            "step": jax.device_put(jnp.zeros((), jnp.int32), repl),
            # runtime lr multiplier (master paral-config pushes): applied
            # to the optimizer's updates inside the jitted step, so the
            # master's sqrt-coupled lr actually takes effect without
            # recompiling (the wd term follows lr — exact decoupled-wd
            # rescaling would need a rebuilt optimizer)
            "lr_scale": jax.device_put(jnp.ones((), jnp.float32), repl),
        }

    def _record_data_parallel_comm(self, params: PyTree):
        """Analytic per-step inventory of the collectives XLA inserts
        for the data axes (profiler/comm.py). These aren't explicit in
        our code — fsdp re-gathers parameters fwd+bwd and reduce-
        scatters gradients; dp all-reduces gradients — so the byte
        counts come from the parameter tree, the same way the
        reference derives NCCL bus bandwidth from algorithm formulas
        rather than observed packets (xpu_timer parse_params.cc).
        ``params`` may be live arrays or their avatars (remesh path)."""
        from dlrover_tpu.profiler.comm import (
            axis_links,
            comm_ledger,
            record_collective,
        )

        # a new trainer means a new program inventory: drop rows from any
        # previous mesh/config so /metrics never mixes dead and live
        # configurations (elastic resize)
        comm_ledger.clear()
        comm_ledger.set_accum_steps(self.accum_steps)
        # per-link classification: on a multislice mesh the dp axis is
        # the one DCN axis; hier-mode events below override per leg
        comm_ledger.set_links(axis_links(self.mesh, self.n_slices))
        shape = dict(self.mesh.shape)
        param_bytes = sum(
            l.size * np.dtype(l.dtype).itemsize
            for l in jax.tree.leaves(params)
        )
        fsdp = shape.get("fsdp", 1)
        if fsdp > 1:
            # ledger unit is PER-SHARD payload per issue (what one rank
            # sends), matching measure_axis_bandwidth's accounting — an
            # fsdp all-gather/reduce-scatter moves 1/fsdp of the params
            # per rank per issue
            record_collective(
                "fsdp.param_all_gather", "all_gather", "fsdp",
                nbytes=param_bytes // fsdp, count=2 * self.accum_steps,
            )
            record_collective(
                "fsdp.grad_reduce_scatter", "reduce_scatter", "fsdp",
                nbytes=param_bytes // fsdp, count=1,
            )
        dp = shape.get("dp", 1)
        if dp > 1:
            mode = self._zero1_mode(self.mesh)
            # grads entering the dp reduction are fsdp-sharded when
            # fsdp>1: per-shard payload is param_bytes/fsdp. Under grad
            # accumulation the partitioner reduces each microbatch's
            # grads inside the scan body (a GSPMD grad is a *global*
            # value the moment value_and_grad returns it — there is no
            # unreduced representation for the accumulator to hold), so
            # the reduction issues once per LOSS CALL, not once per
            # step; the census-diff test (tests/test_zero1.py) pins
            # this inventory against the lowered IR.
            grad_payload = param_bytes // max(fsdp, 1)
            hier_mode = self._hier_mode(self.mesh)
            hier = hier_mode != "flat"
            dp_in = dp // self.n_slices if hier else dp
            # overlap is a SCHEDULE of the hierarchical reduction — the
            # byte inventory below is identical; what changes is how
            # much of the DCN leg sits exposed on the critical path.
            # accum microbatches pipeline accum-1 exchanges behind
            # backward compute (the analytic ratio; the shardcheck
            # overlap dimension proves the scheduled one from the HLO)
            comm_ledger.set_overlap_ratio(
                (self.accum_steps - 1) / self.accum_steps
                if hier_mode == "overlap" and self.accum_steps > 1
                else 0.0
            )
            if hier and mode == "scatter":
                # hierarchical zero-1 (ops/hier_collectives.py): ICI
                # reduce-scatter within the slice, then a DCN
                # reduce-scatter whose cut carries only the slice-local
                # 1/dp_in shard and emits the owned 1/dp moment shard
                record_collective(
                    "dp.grad_reduce_scatter_ici", "reduce_scatter",
                    "dp", nbytes=grad_payload // dp_in, count=1,
                    per="loss_call", link="ici",
                )
                record_collective(
                    "dp.grad_reduce_scatter_dcn", "reduce_scatter",
                    "dp", nbytes=grad_payload // dp, count=1,
                    per="loss_call", link="dcn",
                )
            elif hier:
                # hierarchical replicated: RS (ici) → psum of the
                # 1/dp_in shard (the only DCN leg) → all-gather (ici)
                record_collective(
                    "dp.grad_reduce_scatter_ici", "reduce_scatter",
                    "dp", nbytes=grad_payload // dp_in, count=1,
                    per="loss_call", link="ici",
                )
                record_collective(
                    "dp.grad_allreduce_dcn", "psum", "dp",
                    nbytes=grad_payload // dp_in, count=1,
                    per="loss_call", link="dcn",
                )
                record_collective(
                    "dp.grad_all_gather_ici", "all_gather", "dp",
                    nbytes=grad_payload // dp_in, count=1,
                    per="loss_call", link="ici",
                )
            elif mode == "scatter":
                # explicit psum_scatter straight into the zero-1 layout
                # (train/zero1.py sharded_value_and_grad)
                record_collective(
                    "dp.grad_reduce_scatter", "reduce_scatter", "dp",
                    nbytes=grad_payload // dp, count=1, per="loss_call",
                )
            else:
                # replicated path AND gspmd zero-1: the dp reduction is
                # a psum (under gspmd zero-1 the backend's
                # allreduce-rewrite pass may lower it reduce-scatter;
                # the SC001 census records what actually happened)
                record_collective(
                    "dp.grad_allreduce", "psum", "dp",
                    nbytes=grad_payload, count=1, per="loss_call",
                )
            if mode != "off":
                if hier and mode == "scatter":
                    # hierarchized trailing gather (hier_param_gather):
                    # AG over slice FIRST — the DCN leg carries only
                    # the owned 1/dp shard per issue — then an ICI AG
                    # of the slice-complete 1/dp_in block
                    record_collective(
                        "dp.param_all_gather_dcn", "all_gather", "dp",
                        nbytes=grad_payload // dp, count=1, link="dcn",
                    )
                    record_collective(
                        "dp.param_all_gather_ici", "all_gather", "dp",
                        nbytes=grad_payload // dp_in, count=1,
                        link="ici",
                    )
                else:
                    # zero-1's second half: the dp-sharded updates
                    # gather back into full params once per step
                    record_collective(
                        "dp.param_all_gather", "all_gather", "dp",
                        nbytes=grad_payload // dp, count=1,
                    )

    def _build_step(
        self,
        mesh: Optional[Mesh] = None,
        mesh_config: Optional[MeshConfig] = None,
        out_shardings: Any = None,
    ):
        """The jitted step for ``(mesh, mesh_config)`` — defaults to the
        live pair. Parametrized so the warm-compile path can build the
        step for a mesh that is not (yet) the trainer's.

        ``out_shardings`` (AOT path): pin the output state to the input
        state's shardings. Left to XLA, some outputs come back sharded
        differently than they went in (observed: replicated norm-param
        adam moments returned tp-sharded) — which makes step N+1's
        input signature differ from step N's, silently recompiling
        under jit and hard-rejecting under an AOT executable."""
        mesh = mesh if mesh is not None else self.mesh
        mesh_config = (
            mesh_config if mesh_config is not None else self.mesh_config
        )
        accum = self._accum_for(mesh, mesh_config)
        # the loss must target the step's mesh: a loss closing over a
        # different mesh would bake foreign sharding constraints into
        # this program (cross-world AOT needs the factory form)
        loss_fn = (
            self.loss_factory(mesh)
            if self.loss_factory is not None
            else self.loss_fn
        )
        z1_mode = self._zero1_mode(mesh)
        hier_mode = self._hier_mode(mesh)
        hier = hier_mode != "flat"
        if z1_mode != "off" and self._params_avatar is None:
            # zero-1 derives its per-leaf layout from the param shapes;
            # a step built before any state exists (init_state and
            # record_avatars both set the avatar) has nothing to derive
            # from — and nothing it could run on either
            logger.warning(
                "zero-1 requested but no params avatar captured yet; "
                "building the replicated step"
            )
            z1_mode = "off"
        if hier_mode == "overlap" and self._params_avatar is None:
            # the bucket layout derives from the param shapes, same
            # dependency as zero-1's: degrade to the fused hierarchy
            # (which handles replicated leaves shape-free)
            logger.warning(
                "overlap collectives requested but no params avatar "
                "captured yet; building the fused hierarchical step"
            )
            hier_mode = "hier"
        is_spec = lambda s: isinstance(s, P)  # noqa: E731
        # the params' own layout, as placement targets: pins the f32
        # grad accumulator (a full extra param-sized pytree that used
        # to materialize with NO constraint — replicated under pure dp)
        # and, under zero-1, the post-update param all-gather
        param_put = jax.tree.map(
            lambda s: NamedSharding(mesh, s), self.p_specs,
            is_leaf=is_spec,
        )
        z1_grad_put = None
        z1_grad_fn = None
        if z1_mode != "off":
            axis_sizes = dict(mesh.shape)
            z1_grad_put = jax.tree.map(
                lambda s, av: NamedSharding(
                    mesh,
                    zero1.partition_spec(s, av.shape, axis_sizes) or s,
                ),
                self.p_specs, self._params_avatar, is_leaf=is_spec,
            )
        hier_grad_fn = None
        ov_compute = ov_exchange = None
        gather_fn = None
        if z1_mode == "scatter" and hier:
            # satellite of the hierarchy: the trailing param all-gather
            # runs AG(slice) → AG(dcn-free dp_in) → local unpermute
            # instead of the flat GSPMD gather over the whole dp axis,
            # so its DCN cut carries 1/dp_in of the params
            gather_fn = hier_collectives.hier_param_gather(
                mesh, self._slices_for(mesh), self.p_specs,
                self._params_avatar,
            )
        if hier_mode == "overlap":
            # latency-hiding split of the hierarchy: the eager half
            # (backward + ICI leg) and the deferred half (bucketed DCN
            # exchange) — the step below carries each microbatch's
            # exchange through the scan behind the NEXT backward
            ov_compute, ov_exchange = (
                hier_collectives.overlap_value_and_grad(
                    self.loss_factory(None), mesh,
                    self._slices_for(mesh), self.p_specs,
                    self._params_avatar,
                    zero1_scatter=(z1_mode == "scatter"),
                )
            )
        elif z1_mode == "scatter" and hier:
            # multislice pure-dp: the dp reduction is the two-stage
            # hierarchy — ICI reduce-scatter within the slice, then a
            # DCN reduce-scatter of only the slice-local shard straight
            # into the zero-1 layout (the dp4+2slice+zero1 contract
            # pins the link split)
            z1_grad_fn = hier_collectives.hier_value_and_grad(
                self.loss_factory(None), mesh, self._slices_for(mesh),
                self.p_specs, self._params_avatar, zero1_scatter=True,
            )
        elif z1_mode == "scatter":
            # pure-dp mesh: the loss+grad runs full-manual and the dp
            # reduction is an explicit psum_scatter straight into the
            # zero-1 layout — a REAL reduce-scatter in the lowered HLO
            # on every backend (the dp4+zero1 contract pins it)
            z1_grad_fn = zero1.sharded_value_and_grad(
                self.loss_factory(None), mesh, self.p_specs,
                self._params_avatar,
            )
        elif hier:
            # multislice, replicated weight update: same full-manual
            # engine, grads come back FULL — the DCN cut carries the
            # 1/dp_in shard instead of the whole gradient
            hier_grad_fn = hier_collectives.hier_value_and_grad(
                self.loss_factory(None), mesh, self._slices_for(mesh),
                self.p_specs, None, zero1_scatter=False,
            )

        def step(state, batch):
            # batch: any pytree whose leaves lead with (accum, micro*dp):
            # token arrays for the LM families, (images, labels) for CV
            grad_of = (
                z1_grad_fn if z1_grad_fn is not None
                else hier_grad_fn if hier_grad_fn is not None
                else jax.value_and_grad(loss_fn)
            )
            if ov_compute is not None and accum == 1:
                # single microbatch: no later backward to hide behind —
                # compute and exchange run back-to-back, which IS the
                # fused hierarchical reduction (same ops, bucketed)
                loss_sum, pend = ov_compute(
                    state["params"], jax.tree.map(lambda x: x[0], batch)
                )
                grads = ov_exchange(pend)
            elif ov_compute is not None:
                # the overlap pipeline, peeled: microbatch 0's backward
                # runs outside the scan so every scan iteration pairs
                # the PREVIOUS microbatch's deferred DCN exchange with
                # the CURRENT microbatch's backward — data-independent
                # halves the scheduler is free to run concurrently —
                # and the last exchange flushes after the scan.
                # Addition order matches the fused path exactly:
                # ((0+g0)+g1)+…+g_last into the f32 accumulator.
                acc_put = param_put if z1_mode == "off" else z1_grad_put
                zero = jax.tree.map(
                    lambda p, sh: jax.lax.with_sharding_constraint(
                        jnp.zeros(p.shape, jnp.float32), sh
                    ),
                    state["params"], acc_put,
                )
                loss0, pend0 = ov_compute(
                    state["params"], jax.tree.map(lambda x: x[0], batch)
                )

                def micro_overlap(carry, micro):
                    loss_sum, acc, pend = carry
                    g = ov_exchange(pend)  # previous micro's DCN leg
                    acc = jax.tree.map(jnp.add, acc, g)
                    loss, pend = ov_compute(state["params"], micro)
                    return (loss_sum + loss, acc, pend), None

                (loss_sum, acc, pend), _ = jax.lax.scan(
                    micro_overlap, (loss0, zero, pend0),
                    jax.tree.map(lambda x: x[1:], batch),
                )
                g = ov_exchange(pend)  # flush the last microbatch
                grads = jax.tree.map(jnp.add, acc, g)
            elif accum == 1:
                # single microbatch: no accumulator scan — grads stay in
                # param dtype and the f32 accumulation buffer (a full extra
                # param-sized pytree) is never allocated
                loss_sum, grads = grad_of(
                    state["params"], jax.tree.map(lambda x: x[0], batch)
                )
            else:
                # NB: the model losses may route through the chunked-CE
                # custom_vjp (ops/chunked_ce.py), which itself scans over
                # vocab chunks — custom_vjp rules are opaque to this outer
                # scan's AD, so the grad-accum scan composes with it the
                # same as with any primitive (and the f32 accumulator
                # below absorbs its param-dtype dw chunks via promotion)
                def micro_grads(carry, micro):
                    loss_sum, grads = carry
                    loss, g = grad_of(state["params"], micro)
                    grads = jax.tree.map(jnp.add, grads, g)
                    return (loss_sum + loss, grads), None

                # under zero-1 the accumulator itself lives dp-sharded
                # (1/dp of the f32 tree per device — the same layout the
                # scattered grads and the moments use)
                acc_put = param_put if z1_mode == "off" else z1_grad_put
                zero = jax.tree.map(
                    lambda p, sh: jax.lax.with_sharding_constraint(
                        jnp.zeros(p.shape, jnp.float32), sh
                    ),
                    state["params"], acc_put,
                )
                (loss_sum, grads), _ = jax.lax.scan(
                    micro_grads, (jnp.zeros((), jnp.float32), zero), batch
                )
            scale = 1.0 / accum
            # between the gradients and the update: with
            # optimizer_update below, what the benchmark's optimizer_ms
            # reads off the device trace
            with trace.scope("grad_finish"):
                grads = jax.tree.map(lambda g: g * scale, grads)
                if z1_mode != "off":
                    # the optimizer update runs on the dp shard: grads,
                    # moments (born sharded in init_state) and updates
                    # all carry the zero-1 layout; clip's global norm
                    # reduces a few scalars across dp, nothing
                    # param-sized
                    grads = jax.tree.map(
                        jax.lax.with_sharding_constraint, grads,
                        z1_grad_put,
                    )
            # every instruction of the update carries the scope in its
            # op_name: the device trace's optimizer phase
            # (benchmarks/harness/step_phases.py, optimizer_ms) and the
            # modelled ledger's "optimizer" row
            # (profiler/kernel_ledger.py) both key on it
            with trace.scope("optimizer_update"):
                updates, opt_state = self.optimizer.update(
                    grads, state["opt"], state["params"]
                )
                lr_scale = state.get("lr_scale")
                if lr_scale is not None:
                    updates = jax.tree.map(
                        lambda u: u * lr_scale.astype(u.dtype), updates
                    )
                if z1_mode != "off":
                    updates = jax.tree.map(
                        jax.lax.with_sharding_constraint, updates,
                        z1_grad_put,
                    )
                params = optax.apply_updates(state["params"], updates)
            if z1_mode != "off" and gather_fn is not None:
                # zero-1's second half, hierarchized: pin the summed
                # params to the zero-1 layout (the add runs on the
                # owned shard) and gather explicitly — AG over slice
                # first, so the DCN cut carries 1/dp_in of the params
                # instead of the flat gather's full (1 − 1/s) share
                params = jax.tree.map(
                    jax.lax.with_sharding_constraint, params,
                    z1_grad_put,
                )
                params = gather_fn(params)
                params = jax.tree.map(
                    jax.lax.with_sharding_constraint, params, param_put
                )
            elif z1_mode != "off":
                # zero-1's second half: the dp-sharded updates gather
                # back into the params' own layout — the param
                # all-gather that replaces the grad all-reduce's
                # broadcast half
                params = jax.tree.map(
                    jax.lax.with_sharding_constraint, params, param_put
                )
            out = {
                "params": params,
                "opt": opt_state,
                "step": state["step"] + 1,
            }
            if lr_scale is not None:
                out["lr_scale"] = lr_scale
            return out, loss_sum * scale

        # state keeps the shardings its arrays already carry (params placed
        # by the caller, opt state born sharded in init_state).
        batch_sh = NamedSharding(mesh, P(None, *batch_spec()))
        kwargs = {}
        if out_shardings is not None:
            kwargs["out_shardings"] = out_shardings
        return jax.jit(
            step,
            in_shardings=(None, batch_sh),
            donate_argnums=(0,),
            **kwargs,
        )

    # ---- warm compile (train/warm_compile.py) --------------------------
    def record_avatars(self, state: dict, batch: PyTree):
        """Capture mesh-independent shape/dtype/spec stand-ins for the
        train state and batch. Called automatically on the first
        ``step()``; call it explicitly to AOT-compile before any live
        step has run."""
        self._state_avatar = jax.tree.map(_avatar_of, state)
        self._params_avatar = jax.tree.map(_avatar_of, state["params"])
        self._batch_avatar = jax.tree.map(_avatar_of, batch)

    def _config_hash(self, mesh: Mesh) -> str:
        """Model/config identity for the compile ledger: state-avatar
        shapes+dtypes (the program's real input signature — a model
        change or dtype change re-keys it) plus the trainer knobs that
        shape the step. World-independent except for the zero-1 marker,
        which keys on what the step for ``mesh`` actually builds."""
        parts = [
            f"gb={self.tc.global_batch_size}",
            f"mb={self.tc.micro_batch_size}",
            f"lr={self.tc.learning_rate}",
            f"wd={self.tc.weight_decay}",
            f"clip={self.tc.grad_clip}",
        ]
        if self._zero1_mode(mesh) != "off":
            # asymmetric on purpose: contracts and compile-ledger keys
            # generated before zero-1 existed keep their hashes while
            # the feature is off. Keyed on the EFFECTIVE mode, not the
            # request: a mesh where zero-1 cannot apply (dp<=1, pp>1)
            # builds the replicated program and must hash like it —
            # else a zero1=True config makes that program miss its own
            # checked-in plain contract (a spurious config_hash-mismatch
            # failure, a veto under strict mode)
            parts.append("zero1=1")
        hier_mode = self._hier_mode(mesh)
        if hier_mode != "flat":
            # same asymmetry: the hierarchical step is a genuinely
            # different program (its own +Nslice contract); flat-path
            # hashes — including flat-on-a-multislice-mesh, the
            # kill-switch fallback — stay what they always were
            parts.append(f"hier={self._slices_for(mesh)}")
        if hier_mode == "overlap":
            # the overlap schedule lowers a different program again
            # (bucketed exchanges, peeled scan): its own +overlap
            # contract, its own hash
            parts.append("overlap=1")
        for av in jax.tree.leaves(self._state_avatar):
            parts.append(f"{av.shape}/{av.dtype}")
        return warm_compile.signature_hash(parts)

    def _step_signature(
        self, mesh: Mesh, mesh_config: MeshConfig, accum: int
    ) -> Tuple[str, str]:
        """(in-process cache key, ledger config-hash). The cache key
        pins the exact device assignment: an AOT executable only runs
        on the devices it was compiled for, so a mesh over different
        devices must miss here (and fall through to the persistent
        cache, which keys on topology, not identity)."""
        config_hash = self._config_hash(mesh)
        parts = [
            config_hash,
            str(sorted(mesh.shape.items())),
            # the resolved logical config too: two MeshConfigs resolving
            # over the same physical mesh shape must never share an
            # executable if any future knob differentiates their programs
            str(sorted(mesh_config.resolve(mesh.size).shape().items())),
            str(tuple(d.id for d in mesh.devices.flat)),
            f"accum={accum}",
            # scatter and gspmd lower different programs, and a flag
            # flip between builds must never warm-hit a stale executable
            f"zero1={self._zero1_mode(mesh)}",
            # flat and hier lower different programs too — and the SAME
            # device set re-seated as a different slice count must miss
            f"hier={self._hier_mode(mesh)}x{self._slices_for(mesh)}",
        ]
        for av in jax.tree.leaves(self._state_avatar_for(mesh)):
            parts.append(f"{av.spec}")
        for av in jax.tree.leaves(self._batch_avatar):
            parts.append(f"{av.shape[2:]}/{av.dtype}")
        return warm_compile.signature_hash(parts), config_hash

    def _avatar_args(self, mesh: Mesh, mesh_config: MeshConfig, accum: int):
        """ShapeDtypeStruct (state, batch) pair for ``jit.lower`` on a
        target mesh: state keeps its global shapes with specs re-bound
        to the target mesh; batch leading dims re-derive from the
        target's accumulation split."""
        dp = mesh_config.resolve(mesh.size).data_parallel_size
        # zero-1 aware: the optimizer-state specs re-derive against the
        # TARGET mesh (its dp size decides which dims shard), so the
        # AOT signature, the transfer target and the restore placement
        # all come from the same derivation
        avatar = self._state_avatar_for(mesh)
        state_av = jax.tree.map(
            lambda av: jax.ShapeDtypeStruct(
                av.shape, av.dtype, sharding=NamedSharding(mesh, av.spec)
            ),
            avatar,
        )
        bspec = NamedSharding(mesh, P(None, *batch_spec()))
        batch_av = jax.tree.map(
            lambda av: jax.ShapeDtypeStruct(
                (accum, self.tc.micro_batch_size * dp) + av.shape[2:],
                av.dtype,
                sharding=bspec,
            ),
            self._batch_avatar,
        )
        # output state pinned to the INPUT shardings (same keys the step
        # emits), loss replicated: keeps step N+1's input signature
        # identical to step N's — see _build_step
        out_state_sh = {
            k: jax.tree.map(
                lambda av: NamedSharding(mesh, av.spec),
                avatar[k],
            )
            for k in ("params", "opt", "step", "lr_scale")
            if k in avatar
        }
        out_sh = (out_state_sh, NamedSharding(mesh, P()))
        return state_av, batch_av, out_sh

    def lower_step(
        self,
        mesh: Mesh,
        mesh_config: MeshConfig,
        source: str = "cold",
    ) -> Tuple[Any, dict]:
        """AOT-build the step for ``(mesh, mesh_config)`` — which need
        not be live — via ``jit.lower(avatars).compile()``. Returns
        ``(compiled, info)``; ``info`` records cache disposition and
        compile seconds, which also land in the compile ledger. The
        compiled executable is cached in-process so a later remesh to
        this signature (or a repeat call) is a warm hit; with the
        persistent compilation cache enabled the XLA compile itself is
        also a disk hit across process restarts.

        Requires avatars (one live ``step()`` or ``record_avatars``)."""
        if self._state_avatar is None or self._batch_avatar is None:
            raise RuntimeError(
                "lower_step needs state/batch avatars: run one step() or "
                "call record_avatars(state, batch) first"
            )
        accum = self._accum_for(mesh, mesh_config)
        sig, config_hash = self._step_signature(mesh, mesh_config, accum)
        cached = self.warm.get(sig)
        if cached is not None:
            warm_compile.compile_ledger.record(
                mesh.size, config_hash, 0.0, "warm"
            )
            return cached, {
                "cache": "warm", "compile_s": 0.0,
                "world": mesh.size, "config_hash": config_hash,
            }
        state_av, batch_av, out_sh = self._avatar_args(
            mesh, mesh_config, accum
        )
        # trace spine: every real build (cold AND speculative) is a pair
        # of compile spans; warm hits returned above and cost nothing
        t0 = time.perf_counter()
        # the flash kernels choose their tiles while the step is traced
        # and say so in the attn.* gauges: one build, one count; the
        # loss says how often it forms its logits the same way
        attention.reset_tile_report()
        fused_ce.reset_sweep_report()
        with trace.span("compile", "build.lower", world=mesh.size,
                        source=source, config=config_hash):
            lowered = self._build_step(
                mesh, mesh_config, out_shardings=out_sh
            ).lower(state_av, batch_av)
        # the XLA compile itself, or its load from the persistent cache
        with trace.span("compile", "build.compile", world=mesh.size,
                        source=source, config=config_hash):
            compiled = lowered.compile()
        dt = time.perf_counter() - t0
        with trace.span("compile", "build.checks"):
            # IR-level analysis of the program just built
            # (lint/shardcheck), opted in via DLROVER_TPU_SHARDCHECK.
            # Runs for EVERY lowering — including the speculative
            # neighbor worlds — so a sharding regression on the
            # post-resize mesh is caught before the resize happens, not
            # at its first step. Strict mode raises here, which keeps
            # the poisoned executable out of the cache.
            self._maybe_shardcheck(lowered, compiled, mesh, mesh_config,
                                   config_hash)
            # memory-side analysis of the same build (lint/memcheck.py),
            # opted in via DLROVER_TPU_MEMCHECK: the per-device memory
            # model diffed against its contract and the device-class HBM
            # budget. Strict mode raises BEFORE the cache put, like
            # shardcheck — an executable that cannot fit its budget
            # never becomes a warm hit.
            self._maybe_memcheck(compiled, mesh, mesh_config, config_hash)
        self.warm.put(sig, compiled)
        warm_compile.compile_ledger.record(mesh.size, config_hash, dt, source)
        return compiled, {
            "cache": "miss", "compile_s": dt,
            "world": mesh.size, "config_hash": config_hash,
        }

    # ---- shardcheck (lint/shardcheck.py) -------------------------------
    def _program_of(
        self, lowered, compiled, mesh, config_hash: str,
        mesh_config: Optional[MeshConfig] = None,
    ):
        """Build the shardcheck analysis context from one lowering."""
        from dlrover_tpu.lint import shardcheck

        hints = dict(self.shardcheck_hints)
        if "seq_len" not in hints and self._batch_avatar is not None:
            # token batches lead with (accum, micro*dp, seq): the
            # trailing dim of a rank-3 integer leaf is the sequence
            for av in jax.tree.leaves(self._batch_avatar):
                if len(av.shape) == 3 and np.issubdtype(
                    av.dtype, np.integer
                ):
                    hints["seq_len"] = int(av.shape[2])
                    break
        z1 = self._zero1_mode(mesh) != "off"
        overlap = self._hier_mode(mesh) == "overlap"
        return shardcheck.StepProgram(
            label="hlo:" + self._contract_spec(mesh),
            stablehlo=lowered.as_text(),
            hlo=compiled.as_text(),
            axis_sizes=dict(mesh.shape),
            seq_len=hints.get("seq_len"),
            vocab=hints.get("vocab"),
            world=mesh.size,
            config_hash=config_hash,
            zero1=z1,
            # slice topology for the per-link (ici/dcn) census
            # attribution — passed whenever the mesh is multislice, so
            # even a flat (kill-switch) program's census shows what the
            # slow link carries
            n_slices=self._slices_for(mesh),
            # overlap programs additionally carry the exposed-vs-
            # overlapped DCN-bytes contract dimension
            overlap=overlap,
            accum_steps=self._accum_for(
                mesh,
                mesh_config if mesh_config is not None
                else self.mesh_config,
            ),
            # pipeline-schedule geometry for the SC008 bubble-fraction
            # contract dimension — supplied by callers that know the
            # model's schedule knobs (contract_model)
            pp_schedule=hints.get("pp_schedule"),
        )

    def world_descriptor(self, mesh: Optional[Mesh] = None) -> WorldDescriptor:
        """The ONE description of the world this trainer builds for
        ``mesh`` (default: live): resolved mesh axes x slice count x
        the effective zero-1/hier program modes
        (:class:`~dlrover_tpu.common.world.WorldDescriptor`). Contract
        specs, transfer-target checks and the planner's candidate
        vocabulary all read this instead of re-deriving world shape."""
        mesh = mesh if mesh is not None else self.mesh
        mode = self._hier_mode(mesh)
        hier = mode != "flat"
        return WorldDescriptor.from_axis_sizes(
            dict(mesh.shape),
            n_slices=self._slices_for(mesh) if hier else 1,
            zero1=self._zero1_mode(mesh) != "off",
            hier=hier,
            overlap=(mode == "overlap"),
        )

    def _contract_spec(self, mesh: Mesh) -> str:
        """The SC001 contract key for the program this trainer builds
        on ``mesh``: the mesh spec, ``+Nslice`` when the hierarchical
        strategy is active (a different program with its own census),
        ``+zero1`` when weight-update sharding is on. A multislice mesh
        running the FLAT path keys the plain spec — its census is the
        single-slice program's."""
        return self.world_descriptor(mesh).spec

    def _maybe_shardcheck(
        self, lowered, compiled, mesh, mesh_config, config_hash: str
    ):
        """Lower-time hook: ``DLROVER_TPU_SHARDCHECK`` 0=off, 1=warn,
        2=strict (raise — the build is rejected and nothing enters the
        executable cache). SC001 runs only when a contract for this
        mesh spec exists (``DLROVER_TPU_SHARDCHECK_CONTRACTS`` dir,
        default: the checked-in contracts)."""
        mode = int(flags.SHARDCHECK.get())
        if not mode:
            return
        from dlrover_tpu.lint import shardcheck

        try:
            program = self._program_of(
                lowered, compiled, mesh, config_hash, mesh_config
            )
            contracts_dir = (
                flags.SHARDCHECK_CONTRACTS.get()
                or shardcheck.DEFAULT_CONTRACTS_DIR
            )
            contract = shardcheck.load_contract(
                contracts_dir, self._contract_spec(mesh)
            )
            if (
                contract is not None
                and contract.get("config_hash")
                and contract["config_hash"] != program.config_hash
            ):
                # a contract for the same mesh but a DIFFERENT program
                # (e.g. the checked-in tiny contract-model censuses vs a
                # real model training on dp4): at lower time that means
                # "no contract for this program", not a violation — the
                # CLI, where the program is pinned, keeps the mismatch
                # loud so stale contracts get regenerated
                logger.info(
                    "shardcheck: contract for %s is for config %s (this "
                    "program: %s); SC001 skipped",
                    program.label, contract["config_hash"],
                    program.config_hash,
                )
                contract = None
            violations = shardcheck.check_program(program, contract)
        except Exception as e:
            if isinstance(e, shardcheck.ShardcheckError):
                raise
            # analysis breakage must never take down a training build
            logger.warning("shardcheck hook failed: %s", e)
            return
        if not violations:
            logger.info(
                "shardcheck: %s clean (%s contract)",
                program.label, "with" if contract else "no",
            )
            return
        if mode >= 2:
            raise shardcheck.ShardcheckError(violations)
        for v in violations:
            logger.warning("shardcheck: %s", v.format())

    # ---- memcheck (lint/memcheck.py) -----------------------------------
    def _memcheck_leaves(self, tree):
        """Flatten an avatar pytree into the plain
        :class:`~dlrover_tpu.lint.memcheck.LeafAvatar` records the
        jax-free memory model consumes: pytree path, global shape,
        dtype name, and the flattened mesh axes of the leaf's
        ``PartitionSpec``."""
        from dlrover_tpu.lint import memcheck

        records = []
        flat, _ = jax.tree_util.tree_flatten_with_path(tree)
        for path, av in flat:
            spec = getattr(getattr(av, "sharding", None), "spec", None)
            axes = []
            for entry in tuple(spec) if spec is not None else ():
                if entry is None:
                    continue
                if isinstance(entry, (tuple, list)):
                    axes.extend(str(a) for a in entry)
                else:
                    axes.append(str(entry))
            records.append(memcheck.LeafAvatar(
                path=jax.tree_util.keystr(path),
                shape=tuple(int(d) for d in av.shape),
                dtype=np.dtype(av.dtype).name,
                sharded_axes=tuple(axes),
            ))
        return records

    def _memcheck_payload_of(
        self, compiled, mesh, mesh_config, config_hash: str
    ) -> dict:
        """The static per-device memory model of one compiled build:
        guarded ``memory_analysis()`` bytes plus the analytic per-leaf
        breakdown that explains them (lint/memcheck.py)."""
        from dlrover_tpu.lint import memcheck

        accum = self._accum_for(mesh, mesh_config)
        state_av, batch_av, _ = self._avatar_args(mesh, mesh_config, accum)
        spec = self._contract_spec(mesh)
        measured = memcheck.read_memory_analysis(
            compiled, label=f"mem:{spec}"
        )
        components = memcheck.analytic_components(
            self._memcheck_leaves(state_av),
            self._memcheck_leaves(batch_av),
            dict(mesh.shape),
            measured,
        )
        payload = {
            "mesh_spec": spec,
            "config_hash": config_hash,
            "world": int(mesh.size),
            "axis_sizes": {a: int(s) for a, s in dict(mesh.shape).items()},
            "components": components,
            "peak_bytes": memcheck.analytic_peak_bytes(components),
            "measured": measured,
        }
        delta = memcheck.explain_delta_frac(components, measured)
        if delta is not None:
            payload["argument_delta_frac"] = round(delta, 4)
        return payload

    def memcheck_payload(self, mesh=None, mesh_config=None) -> dict:
        """Build (AOT, host-only — warm cache makes repeats free) the
        step for ``(mesh, mesh_config)`` and return its memory payload.
        The CLI ``--mem`` mode's entry point:
        like ``step_ir``, the substrate for any admissible world comes
        from the avatars, so no TPU — and no live training process —
        is needed."""
        mesh = mesh if mesh is not None else self.mesh
        mesh_config = (
            mesh_config if mesh_config is not None else self.mesh_config
        )
        compiled, info = self.lower_step(mesh, mesh_config,
                                         source="memcheck")
        return self._memcheck_payload_of(
            compiled, mesh, mesh_config, info["config_hash"]
        )

    def _headroom_oracle(
        self, device_class: str = "", budget_gb: float = 0.0
    ):
        """The live program's static headroom oracle: the analytic
        components at the CURRENT mesh lifted to global totals, so any
        candidate world prices out without compiling it
        (:class:`~dlrover_tpu.lint.memcheck.HeadroomOracle`)."""
        from dlrover_tpu.lint import memcheck

        accum = self._accum_for(self.mesh, self.mesh_config)
        state_av, batch_av, _ = self._avatar_args(
            self.mesh, self.mesh_config, accum
        )
        components = memcheck.analytic_components(
            self._memcheck_leaves(state_av),
            self._memcheck_leaves(batch_av),
            dict(self.mesh.shape),
        )
        wd = self.world_descriptor(self.mesh)
        return memcheck.HeadroomOracle.from_components(
            components, wd,
            device_class=device_class, budget_gb=budget_gb,
            # candidates run the current program family: a bare-dp
            # neighbor descriptor still packs moments like this build
            assume_zero1=wd.zero1,
        )

    def _maybe_memcheck(self, compiled, mesh, mesh_config,
                        config_hash: str):
        """Lower-time hook, fifth invariant layer:
        ``DLROVER_TPU_MEMCHECK`` 0=off, 1=warn, 2=strict (raise — the
        build is rejected and nothing enters the executable cache).
        MC001 runs only when a ``mem-<spec>`` contract for this program
        exists (``DLROVER_TPU_MEMCHECK_CONTRACTS`` dir, default: the
        checked-in contracts); MC002 only when a device class or
        explicit budget is configured."""
        mode = int(flags.MEMCHECK.get())
        if not mode:
            return
        from dlrover_tpu.lint import memcheck

        try:
            payload = self._memcheck_payload_of(
                compiled, mesh, mesh_config, config_hash
            )
            label = "mem:" + payload["mesh_spec"]
            contracts_dir = (
                flags.MEMCHECK_CONTRACTS.get()
                or memcheck.DEFAULT_CONTRACTS_DIR
            )
            contract = memcheck.load_mem_contract(
                contracts_dir, payload["mesh_spec"]
            )
            if (
                contract is not None
                and contract.get("config_hash")
                and contract["config_hash"] != payload["config_hash"]
            ):
                # same mesh, different program (the checked-in tiny
                # contract-model breakdowns vs a real model): at lower
                # time that means "no contract", not a violation —
                # mirror of the shardcheck hook's rule
                logger.info(
                    "memcheck: contract for %s is for config %s (this "
                    "program: %s); MC001 skipped",
                    label, contract["config_hash"],
                    payload["config_hash"],
                )
                contract = None
            violations = []
            if contract is not None:
                violations.extend(memcheck.check_components(
                    payload["components"], payload["peak_bytes"],
                    contract, label=label,
                ))
            violations.extend(memcheck.check_budget(
                payload["peak_bytes"],
                device_class=flags.MEMCHECK_DEVICE_CLASS.get(),
                budget_gb=float(flags.MEMCHECK_BUDGET_GB.get()),
                label=label,
            ))
        except Exception as e:
            if isinstance(e, memcheck.MemcheckError):
                raise
            # analysis breakage must never take down a training build
            logger.warning("memcheck hook failed: %s", e)
            return
        if not violations:
            logger.info(
                "memcheck: %s clean (%s contract, peak %d bytes/device)",
                label, "with" if contract else "no",
                payload["peak_bytes"],
            )
            return
        if mode >= 2:
            raise memcheck.MemcheckError(violations)
        for v in violations:
            logger.warning("memcheck: %s", v.format())

    def step_ir(self, mesh=None, mesh_config=None, pinned: bool = True):
        """Lower (and compile — on the host, no device execution) the
        step for ``(mesh, mesh_config)`` and return the shardcheck
        ``StepProgram`` for it. This is the CLI / CI entry: the
        analysis substrate for any admissible world comes from the same
        avatars the warm-compile path lowers from, so none of it needs
        a live training process — or a TPU.

        ``pinned=False`` builds the step WITHOUT pinned out_shardings
        (the kill-switch jit path), which SC004 flags — used by tests
        to demonstrate the drift gate."""
        mesh = mesh if mesh is not None else self.mesh
        mesh_config = (
            mesh_config if mesh_config is not None else self.mesh_config
        )
        if self._state_avatar is None or self._batch_avatar is None:
            raise RuntimeError(
                "step_ir needs state/batch avatars: run one step() or "
                "call record_avatars(state, batch) first"
            )
        accum = self._accum_for(mesh, mesh_config)
        _, config_hash = self._step_signature(mesh, mesh_config, accum)
        state_av, batch_av, out_sh = self._avatar_args(
            mesh, mesh_config, accum
        )
        lowered = self._build_step(
            mesh, mesh_config, out_shardings=out_sh if pinned else None
        ).lower(state_av, batch_av)
        return self._program_of(
            lowered, lowered.compile(), mesh, config_hash, mesh_config
        )

    def _acquire_step_fn(self):
        """The step for the live mesh: plain jit when the kill-switch
        is off; otherwise the AOT path — in-process warm hit when this
        signature compiled before (speculative neighbor compile, a
        remesh back to a previous world), cold AOT compile otherwise —
        followed by a speculative kick for the neighbor worlds."""
        self._last_build_info = {"cache": "jit", "compile_s": None}
        if not warm_compile.warm_compile_enabled():
            return self._build_step()
        try:
            fn, info = self.lower_step(self.mesh, self.mesh_config)
        except Exception as e:
            # strict shardcheck/memcheck is a deliberate veto of this
            # program — falling back to plain jit would run the exact
            # program the check just rejected
            from dlrover_tpu.lint import memcheck, shardcheck

            if isinstance(
                e, (shardcheck.ShardcheckError, memcheck.MemcheckError)
            ):
                raise
            logger.exception(
                "AOT step build failed; falling back to plain jit"
            )
            return self._build_step()
        self._last_build_info = info
        # the live step's memory per device as the compiler planned it:
        # the backend's own peak counter leaves out the program's
        # temporaries
        from dlrover_tpu.lint import memcheck

        measured = memcheck.read_memory_analysis(fn)
        for key in ("peak_bytes", "planned_peak_bytes", "temp_bytes",
                    "argument_bytes"):
            if key in measured:
                trace.gauge(f"step.hbm_{key}", measured[key])
        # a device trace names instructions, not the scopes they came
        # from: the compiled text has both, for whoever reads a trace
        trace.provide_text("step.hlo", fn.as_text)
        if info["cache"] == "warm":
            logger.info(
                "step build: WARM (AOT cache hit, world=%d)", self.mesh.size
            )
        else:
            logger.info(
                "step build: cold compile %.2fs (world=%d config=%s)",
                info["compile_s"], self.mesh.size, info["config_hash"],
            )
        with trace.span("compile", "build.speculate"):
            self._maybe_speculate()
        return fn

    def _descriptor_for_world(
        self, world: int, n_slices: Optional[int] = None
    ) -> Optional[WorldDescriptor]:
        """Refit this trainer's mesh config onto ``world`` devices and
        describe the result, or None when the world is inadmissible
        (model axes don't fit, global-batch invariant broken, devices
        unavailable) — the same filters ``neighbor_worlds`` applies, so
        a planner hint survives exactly when a neighbor would."""
        from dlrover_tpu.parallel.mesh import remesh as remesh_config

        if world <= 0 or world > jax.device_count():
            return None
        slices = (
            max(1, int(n_slices)) if n_slices is not None
            else self._slices_for_size(world)
        )
        if slices > 1 and world % slices:
            return None
        try:
            resolved = remesh_config(self.mesh_config, world).resolve(world)
        except ValueError:
            return None
        dp = resolved.data_parallel_size
        if self.tc.global_batch_size % (self.tc.micro_batch_size * dp):
            return None
        if slices > 1 and dp % slices:
            return None
        try:
            return WorldDescriptor.from_axis_sizes(
                resolved.shape(), n_slices=slices, hier=slices > 1
            )
        except ValueError:
            return None

    def set_speculation_hint(self, hint, n_slices: Optional[int] = None):
        """Planner-directed speculation (brain/planner.py): tell the
        warm compiler which EXACT world the master's goodput planner
        intends to resize to next, so the background thread compiles
        that target first — a planner-directed resize then lands on a
        pre-compiled executable instead of hoping the blind ±node/±slice
        neighbor enumeration guessed right.

        ``hint``: a :class:`WorldDescriptor`, a device-world size (the
        caller converts the master's node-level hint via its local
        device count), or None to clear. Inadmissible hints (model axes
        don't fit, batch invariant broken) are dropped — the neighbor
        heuristic remains the fallback either way."""
        if hint is None:
            self._speculation_hint = None
            return
        if isinstance(hint, WorldDescriptor):
            wd = self._descriptor_for_world(
                hint.world_size, n_slices=hint.n_slices
            )
        else:
            wd = self._descriptor_for_world(int(hint), n_slices=n_slices)
        if wd is not None and wd.world_size == self.mesh.size:
            wd = None  # already there — nothing to pre-compile
        if wd is not None:
            logger.info(
                "speculation hint armed: planner intends world %s",
                wd.spec,
            )
        self._speculation_hint = wd

    def _maybe_speculate(self):
        """After a successful live build, compile the step for likely
        next worlds in the background (bounded daemon thread; skips
        when the kill-switch is off or no persistent cache dir is
        configured — see WarmCompiler.speculate). A planner speculation
        hint (``set_speculation_hint``) takes the FIRST slot — the
        planner said which world comes next, so that exact target gets
        compiled before any blind neighbor; without a hint the neighbor
        enumeration behaves exactly as before. Needs the factory form
        of the loss: a plain ``loss_fn`` may close over the live mesh
        and cannot be retargeted to another world."""
        if self.loss_factory is None:
            return
        try:
            targets = warm_compile.neighbor_worlds(
                self.mesh.size,
                self.mesh_config,
                n_devices_available=jax.device_count(),
                devices_per_node=jax.local_device_count(),
                global_batch_size=self.tc.global_batch_size,
                micro_batch_size=self.tc.micro_batch_size,
                n_slices=self.n_slices,
            )
        except Exception:
            return
        hint = self._speculation_hint
        if hint is not None and hint.world_size != self.mesh.size:
            targets = [hint] + [
                t for t in targets if t.world_size != hint.world_size
            ]
        targets = self._filter_speculation_targets(targets)
        if not targets:
            return

        def compile_for_world(wd: WorldDescriptor):
            from dlrover_tpu.parallel.mesh import config_for, mesh_for

            # multislice: a neighbor world is a whole number of slices
            # (the descriptor checked it) — mesh_for builds it
            # slice-major so the speculated executable IS the
            # post-slice-loss program (the hierarchical strategy and
            # the ici/dcn layout both key on it) and re-checks the
            # built mesh against the descriptor
            mesh = mesh_for(wd)
            _, info = self.lower_step(
                mesh, config_for(wd), source="speculative"
            )
            # no log once shutdown began: the interpreter may have
            # closed the log streams under this daemon thread
            if info["cache"] == "miss" and not self.warm._stop.is_set():
                logger.info(
                    "speculative compile: world=%s ready in %.2fs",
                    wd.spec, info["compile_s"],
                )

        if self.warm.speculate(targets, compile_for_world):
            logger.info(
                "speculating step compiles for worlds %s%s",
                [t.spec for t in targets],
                " (planner-hinted)" if hint is not None else "",
            )

    def _filter_speculation_targets(self, targets):
        """memcheck's static headroom oracle over the speculative
        worlds: drop neighbors whose predicted per-device peak cannot
        fit the configured device-class budget, so no AOT compile is
        wasted on a world the planner would oom-veto anyway. Unarmed
        (no ``DLROVER_TPU_MEMCHECK_DEVICE_CLASS`` / ``_BUDGET_GB``) ->
        targets pass through untouched."""
        from dlrover_tpu.lint import memcheck

        device_class = flags.MEMCHECK_DEVICE_CLASS.get()
        budget_gb = float(flags.MEMCHECK_BUDGET_GB.get())
        if memcheck.budget_bytes(device_class, budget_gb) <= 0:
            return targets
        try:
            oracle = self._headroom_oracle(
                device_class=device_class, budget_gb=budget_gb
            )
        except Exception as e:
            logger.warning("memcheck speculation oracle failed: %s", e)
            return targets
        kept = []
        for wd in targets:
            verdict = oracle.fits(wd)
            if verdict["fits"]:
                kept.append(wd)
            else:
                logger.info(
                    "speculation: skipping world %s (memcheck oom "
                    "veto: predicted %d > usable %d bytes)",
                    wd.spec, verdict["peak_bytes"],
                    verdict["usable_bytes"],
                )
        return kept

    def apply_paral_config(self, state: dict, config: dict) -> dict:
        """Apply a master-pushed runtime config to the train state: a new
        ``optimizer_learning_rate`` becomes an update multiplier relative
        to the configured base lr (the schedule shape is preserved). The
        dataloader fields are consumed by ``ElasticDataLoader``."""
        # host dict read, not a device sync  # graftlint: disable=JG002
        new_lr = float(config.get("optimizer_learning_rate", 0.0) or 0.0)
        if new_lr > 0 and self.tc.learning_rate > 0 and "lr_scale" in state:
            scale = new_lr / self.tc.learning_rate
            # intentional sync: throttled to every poll interval (~100
            # steps) by poll_runtime_config  # graftlint: disable=JG002
            if abs(scale - float(state["lr_scale"])) > 1e-9:
                state = {
                    **state,
                    "lr_scale": jax.device_put(
                        jnp.asarray(scale, jnp.float32),
                        NamedSharding(self.mesh, P()),
                    ),
                }
                from dlrover_tpu.common.log import logger as _logger

                _logger.info(
                    "runtime lr update: base=%g -> %g (scale %.4f)",
                    self.tc.learning_rate, new_lr, scale,
                )
        return state

    def poll_runtime_config(
        self, state: dict, every_steps: int = 100
    ) -> dict:
        """Cheap per-step hook: every ``every_steps`` host steps re-read
        the agent-pushed paral config file and apply optimizer changes."""
        if self._host_step % max(1, every_steps):
            return state
        from dlrover_tpu.agent.paral_config_tuner import read_paral_config

        config = read_paral_config()
        version = int(config.get("optimizer_version", 0) or
                      config.get("dataloader_version", 0) or 0)
        if config and version != self._applied_config_version:
            self._applied_config_version = version
            state = self.apply_paral_config(state, config)
        # the goodput planner's speculation hint rides the same
        # throttled cadence (brain/planner.py): one cheap membership
        # poll per ~every_steps host steps arms the warm compiler with
        # the exact world the planner intends next, so the directed
        # resize lands warm. Contexts without the helper (older stubs,
        # tests) are skipped; failures never touch the training loop.
        if self.worker_ctx is not None and hasattr(
            self.worker_ctx, "poll_speculation_hint"
        ):
            try:
                self.worker_ctx.poll_speculation_hint(self)
            except Exception:
                pass
        return state

    def eval_step(self, state: dict, batch) -> jnp.ndarray:
        """Loss of one batch WITHOUT touching the train state: jitted
        forward-only, no donation (state survives), batch shaped
        (micro*dp, ...) — one microbatch row of ``step_batch_shape``."""
        if self._eval_fn is None:
            bspec = batch_spec()
            self._eval_fn = jax.jit(
                lambda params, b: self.loss_fn(params, b),
                in_shardings=(
                    None, NamedSharding(self.mesh, P(*bspec)),
                ),
            )
        return self._eval_fn(state["params"], batch)

    def evaluate(self, state: dict, batches) -> float:
        """Mean loss over an iterable of eval batches (each shaped like
        one ``step_batch_shape`` row). The evaluator-role analogue of the
        reference's estimator evaluation: the same jitted graph and mesh
        as training, params untouched, no optimizer state involved.

        Losses accumulate ON DEVICE and convert to a host float once at
        the end: a per-batch ``float()`` would block on every batch's
        just-dispatched forward, serializing host and device (async
        dispatch is the whole point of the jitted eval)."""
        total = None
        count = 0
        with trace.span("eval", "evaluate"):
            for batch in batches:
                loss = self.eval_step(state, batch)
                total = loss if total is None else total + loss
                count += 1
        if count == 0:
            # 0.0 would read as a perfect loss to early-stopping logic
            raise ValueError(
                "evaluate() got zero batches (eval dataset smaller than "
                "one batch under drop_last?)"
            )
        return float(total) / count

    def _dispatch(self, state: dict, batch):
        """Hand the step to the device; returns before it has run."""
        try:
            return self._step_fn(state, batch)
        except (ValueError, TypeError) as e:
            # an AOT executable (warm path) is stricter than jit: a
            # committed input with a different sharding raises
            # ValueError("...does not match..."), and a batch with a
            # different shape/dtype raises TypeError("Argument types
            # differ from the types for which this computation was
            # compiled") where jit would silently recompile. Rebuild
            # via plain jit once rather than fail training over it.
            msg = str(e)
            if not warm_compile.warm_compile_enabled() or not (
                "does not match" in msg
                or "differ from the types" in msg
            ):
                raise
            logger.warning(
                "AOT step rejected input shardings (%s); rebuilding with "
                "plain jit", str(e)[:200],
            )
            # evict the poisoned executable: a later remesh back to this
            # signature must not warm-hit it and fail again
            try:
                sig, _ = self._step_signature(
                    self.mesh, self.mesh_config, self.accum_steps
                )
                self.warm.evict(sig)
            except Exception:
                pass
            # the AOT info (possibly a 0.0s warm hit) no longer describes
            # this build: route _finalize_resize to the measured branch
            self._last_build_info = {"cache": "jit", "compile_s": None}
            self._step_fn = self._build_step()
            return self._step_fn(state, batch)

    def step(self, state: dict, batch) -> Tuple[dict, jnp.ndarray]:
        """One optimizer step = ``accum_steps`` microbatches.

        ``batch``: any pytree whose leaves lead with (accum_steps,
        micro*dp, ...) — int32 token arrays for the LM families,
        (images, labels) tuples for CV."""
        first_build = self._step_fn is None
        build_t0 = time.perf_counter()
        if first_build:
            # the first call is build-dominated: it has a span of its
            # own and stays out of `train_step`, of the step rows and of
            # the digest, or every (re)start would feed the straggler
            # detector one giant sample per rank
            self._account.reset()
            with trace.span("host", "first_step", step=self._host_step + 1):
                with trace.span("compile", "build",
                                world=self.mesh.size) as built:
                    with trace.span("compile", "build.avatars"):
                        self.record_avatars(state, batch)
                    self._step_fn = self._acquire_step_fn()
                    built.set(cache=self._last_build_info["cache"])
                if self.worker_ctx is not None:
                    state = self.poll_runtime_config(state)
                new_state, loss = self._dispatch(state, batch)
        else:
            if self.worker_ctx is not None:
                state = self.poll_runtime_config(state)
            with trace.span("step", "train_step", step=self._host_step + 1,
                            host_step=self._host_step + 1) as dispatched:
                new_state, loss = self._dispatch(state, batch)
                # step wall clock, measured WITHOUT a device sync: from
                # the last dispatch's return to this one's is the whole
                # loop, which is the device's step for a caller that
                # fetches every loss (it waits for it in between) and
                # for one that runs ahead (dispatch of step N blocks on
                # donation until step N-1's buffers free). The dispatch
                # span alone is neither: microseconds for a caller that
                # fetches. The account closes here, after the dispatch:
                # the device has its work, so the probes cost the step
                # nothing. The row feeds the per-rank digest; none
                # closes at the first call after a build, whose interval
                # would hold the build.
                row = self._account.close(self._host_step + 1, dispatched)
                if row is not None:
                    if not row["edge"]:
                        self.step_digest.add(row["interval_s"],
                                             row["late_s"])
                    # on the profiler's host plane this event lies beside
                    # the end of the device work that the interval it
                    # closes waited for
                    dispatched.set(
                        prev_interval_ms=row["interval_s"] * 1e3,
                        prev_gc_ms=sum(row["gc_s"]) * 1e3,
                        prev_runq_ms=(row["runq_s"] or 0.0) * 1e3,
                        prev_cpu_ms=row["cpu_s"] * 1e3,
                        prev_named_ms=row["named_s"] * 1e3,
                    )
        if first_build and self._pending_resize is not None:
            self._finalize_resize(loss, build_t0)
        # host-side step counter: reading new_state["step"] would block on
        # the just-dispatched computation and kill async dispatch
        self._host_step += 1
        if self.worker_ctx is not None:
            try:
                self.worker_ctx.report_step(
                    self._host_step, digest=self.step_digest
                )
            except TypeError:
                # digest-unaware context (older stubs): plain report
                self.worker_ctx.report_step(self._host_step)
        if self._retrace_guard is not None:
            # violations from background (speculative-compile) threads
            # can't raise in place; surface them at the step boundary
            self._retrace_guard.check()
        return new_state, loss

    def sync_host_step(self, state: dict):
        """Seed the host-side step counter from a restored train state.

        Call this from the restore path (after ``ckpt.load``): without
        it ``_host_step`` restarts at 0 and ``report_step`` feeds the
        master's SpeedMonitor a regressing global step after every
        restart, corrupting goodput accounting. The one host sync here
        is fine — restore already synchronized."""
        step = state.get("step") if isinstance(state, dict) else None
        if step is None:
            return
        self._host_step = int(jax.device_get(step))
        logger.info("host step counter seeded from restore: %d",
                    self._host_step)

    def _finalize_resize(self, loss, build_t0: float):
        """Close the resize event the last ``remesh()`` opened: stamp the
        compile half of the downtime breakdown and publish the event to
        the resize ledger (+ the master, when connected).

        The AOT path reports its exact compile seconds. The plain-jit
        path compiles lazily inside the first call — so, once per
        resize, block for the just-dispatched step and attribute the
        wall time to compile (the execute tail is noise next to a real
        model's compile; a resize boundary already synchronized for the
        state transfer, so this one sync costs nothing extra)."""
        pending, self._pending_resize = self._pending_resize, None
        info = getattr(self, "_last_build_info", None) or {}
        compile_s = info.get("compile_s")
        # ONE clock read for every synthetic span below: re-reading the
        # clock per span would let a later span's back-dated start land
        # inside an earlier one by the microseconds between the reads
        # (the job-timeline --check enforces nesting per lane). The
        # synthetic spans also live on their own "resize" lane so they
        # can never partially overlap the real thread-lane spans.
        now_m = time.monotonic()
        if compile_s is None:
            # jit (kill-switch / AOT-fallback) path; the AOT path's
            # compile span came from lower_step, this lazy-jit compile
            # only becomes measurable here
            jax.block_until_ready(loss)  # graftlint: disable=JG002
            compile_s = time.perf_counter() - build_t0
            now_m = time.monotonic()  # after the sync, before any span
            trace.record(
                "compile", "resize.first_step_compile",
                now_m - compile_s, compile_s, tid="resize",
                world=pending["to"], source="resize-jit",
            )
        # the rendezvous half was measured by the caller (remesh's
        # rendezvous_s) — lay it strictly before the transfer+compile
        # so the local timeline shows the whole downtime bracket
        # host dict reads, not device syncs  # graftlint: disable=JG002
        rdzv_s = float(pending.get("rendezvous_s", 0.0) or 0.0)
        if rdzv_s > 0:
            before = compile_s + float(  # graftlint: disable=JG002
                pending.get("state_transfer_s", 0.0) or 0.0
            )
            trace.record(
                "rendezvous", "resize.rendezvous",
                now_m - before - rdzv_s, rdzv_s, tid="resize",
                world=pending["to"],
            )
        event = live_reshard.resize_ledger.record(
            pending["from"], pending["to"],
            rendezvous_s=pending.get("rendezvous_s", 0.0),
            compile_s=compile_s,
            state_transfer_s=pending.get("state_transfer_s", 0.0),
            path=pending.get("path", "checkpoint"),
            restore_tier=pending.get("restore_tier", ""),
        )
        logger.info(
            "resize %d->%d downtime breakdown: compile=%.3fs "
            "state_transfer=%.3fs (path=%s, restore_tier=%s)",
            event["world_from"], event["world_to"], event["compile_s"],
            event["state_transfer_s"], event["path"],
            event["restore_tier"] or "?",
        )
        if self.worker_ctx is not None:
            self.worker_ctx.report_resize_breakdown(
                rendezvous_s=event["rendezvous_s"],
                compile_s=event["compile_s"],
                state_transfer_s=event["state_transfer_s"],
                restore_tier=event["restore_tier"],
            )

    def note_restore_tier(self, tier: str):
        """Stamp which checkpoint tier supplied the state for the resize
        in flight (``engine.last_restore_stats["tier"]``). Call between
        ``remesh()`` (when it returned None — the checkpoint path) and
        the first post-resize ``step()``; the breakdown event then
        attributes the downtime-ending restore to its tier."""
        if self._pending_resize is not None and tier:
            self._pending_resize["restore_tier"] = str(tier)

    # ---- elasticity ----------------------------------------------------
    def remesh(
        self,
        mesh: Mesh,
        mesh_config: MeshConfig,
        state: Optional[dict] = None,
        rendezvous_s: float = 0.0,
        n_slices: Optional[int] = None,
    ) -> Optional[dict]:
        """After a membership change: adopt the new mesh; the jitted step is
        rebuilt (recompiled) lazily; accumulation re-derives so the global
        batch is unchanged (the reference's core elasticity invariant).

        ``state`` (live-reshard path): when the old state is still on
        device — the process survived the resize — pass it here and the
        trainer moves it old-mesh→new-mesh device-to-device (batched
        ``jax.device_put`` against the avatar-derived target shardings,
        with a leaf-wise + host-bridge fallback ladder), skipping the
        checkpoint round-trip entirely. Returns the transferred state,
        or None when live reshard is off / unavailable — the caller
        then restores via the checkpoint engine exactly as before.

        ``rendezvous_s``: seconds the caller spent re-seating the world
        before calling here (the agent/worker measured the
        re-rendezvous); stamped into the pending resize event so the
        breakdown report and the trace spine carry the rendezvous half
        of the downtime bracket instead of a hardcoded zero.

        ``n_slices``: the new world's slice count (a slice loss resizes
        it). ``None`` keeps the slices-are-atomic derivation — the new
        world re-tiles into the old per-slice size where possible, else
        single-slice (a caller that knows better passes it)."""
        old = self.accum_steps
        dp = mesh_config.resolve(mesh.size).data_parallel_size
        denom = self.tc.micro_batch_size * dp
        if self.tc.global_batch_size % denom:
            raise ValueError(
                f"cannot remesh to world={mesh.size}: global_batch="
                f"{self.tc.global_batch_size} not divisible by "
                f"micro_batch*dp={denom}; trainer left on the old mesh"
            )
        old_world = self.mesh.size
        new_state: Optional[dict] = None
        transfer_info: Optional[dict] = None
        if state is not None and live_reshard.live_reshard_enabled():
            # transfer BEFORE adopting the new mesh fails nothing if the
            # ladder falls through: state stays placed for the old mesh
            # and the caller's checkpoint restore path is untouched
            try:
                if self._state_avatar is None:
                    self._state_avatar = jax.tree.map(_avatar_of, state)
                if self._params_avatar is None and "params" in state:
                    # zero-1 derives its layout from the params avatar;
                    # leaving it unseeded here would downgrade the next
                    # _build_step to the replicated path while the
                    # signature/ledger/contracts still say zero-1
                    self._params_avatar = jax.tree.map(
                        _avatar_of, state["params"]
                    )
                # zero-1 aware retarget: the new dp size (or a zero-1
                # on/off flip taking effect at this resize boundary)
                # re-derives every moment's layout, so dp-sharded
                # moments remesh device-to-device like any other leaf —
                # including the zero↔off transitions
                avatars = self._state_avatar_for(mesh)
                # check the built mesh against the descriptor derived
                # from the CONFIG (the independent source — deriving it
                # from mesh.shape would compare the mesh with itself):
                # a caller passing a mesh inconsistent with the config
                # it also passed fails here, before any state moves
                target_world = WorldDescriptor.from_axis_sizes(
                    mesh_config.resolve(mesh.size).shape()
                )
                shardings = live_reshard.state_shardings(
                    avatars, mesh, world=target_world
                )
                new_state, transfer_info = live_reshard.transfer_state(
                    state, shardings
                )
            except Exception as e:
                logger.warning(
                    "live reshard %d->%d failed (%s); caller should "
                    "restore from checkpoint", old_world, mesh.size, e,
                )
                new_state = None
        new_slices = (
            max(1, int(n_slices)) if n_slices is not None
            else self._slices_for_size(mesh.size)
        )
        self.mesh = mesh
        self.mesh_config = mesh_config
        self.n_slices = new_slices
        self._step_fn = None
        self._eval_fn = None  # its NamedSharding binds the old mesh
        if (
            self._speculation_hint is not None
            and self._speculation_hint.world_size == mesh.size
        ):
            # the hinted resize happened — the hint is consumed (the
            # next build's speculation goes back to neighbors until the
            # planner publishes a new intent)
            self._speculation_hint = None
        self._pending_resize = {
            "from": old_world,
            "to": mesh.size,
            "rendezvous_s": max(0.0, float(rendezvous_s)),
            "state_transfer_s": (
                transfer_info["transfer_s"] if transfer_info else 0.0
            ),
            "path": (
                transfer_info["path"] if transfer_info else "checkpoint"
            ),
            # "live" = no restore happened at all; the checkpoint path
            # stamps its tier via note_restore_tier once the caller's
            # engine.load() reports which rung supplied the state
            "restore_tier": "live" if transfer_info else "",
        }
        if self.loss_factory is not None:
            # re-derive the loss for the new mesh (a loss closing over
            # the old mesh would pin its sharding constraints to dead
            # devices and poison the rebuild)
            self.loss_fn = self.loss_factory(mesh)
        # refresh the comm inventory NOW: on the elastic resize path the
        # state is restored (init_state never runs again), and without
        # this /metrics keeps advertising the dead mesh's collectives
        # and accumulation count
        if self._params_avatar is not None:
            self._record_data_parallel_comm(self._params_avatar)
        warm = False
        if (
            warm_compile.warm_compile_enabled()
            and self._state_avatar is not None
            and self._batch_avatar is not None
        ):
            try:
                sig, _ = self._step_signature(
                    mesh, mesh_config, self.accum_steps
                )
                warm = self.warm.get(sig) is not None
            except Exception:
                warm = False
        logger.info(
            "remesh: world=%d accum %d→%d (global batch fixed at %d); "
            "step rebuild will be %s; state %s",
            mesh.size, old, self.accum_steps, self.tc.global_batch_size,
            "WARM (AOT executable cached)" if warm else "cold",
            (
                f"live-resharded in {transfer_info['transfer_s']:.3f}s "
                f"({transfer_info['path']})"
                if transfer_info
                else "NOT transferred (checkpoint restore path)"
            ),
        )
        if new_state is not None:
            # the transfer already synchronized; re-seeding the host
            # step counter here keeps report_step monotonic across the
            # resize without a checkpoint restore to do it
            self.sync_host_step(new_state)
        return new_state
