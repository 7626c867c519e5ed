"""Host-offloaded, gradient-accumulating train step (single chip or dp).

TPU-native form of the reference's optimizer-state CPU offload + gradient
merge (`sharding/offload_helper.py`, `sharding_optimizer.py:464`
_apply_optimize_offload_pass, `GradientMergeOptimizer optimizer.py:6780`):
optimizer moments (and fp32 master weights) live in PINNED HOST memory
between steps; K compiled micro-steps accumulate f32 gradients on device;
the optimizer update then streams per layer-sized CHUNK through HBM —
H2D states -> fused update -> D2H states — so peak HBM holds

    params + grad accumulators + ONE chunk of optimizer state

instead of params + grads + the full moments. This is what makes a full
GPT-1.3B train step (bf16 params 2.6 GB, f32 accum 5.2 GB, f32
master+moments 15.6 GB on HOST) fit a single 16 GB v5e chip; the fused
`ShardedTrainStep` necessarily materializes every state as a live program
input and cannot.

Chunk updates are issued asynchronously in dispatch order, so chunk i+1's
H2D overlaps chunk i's update compute; identical-structure chunks (the 24
transformer blocks) share one compiled update program via shape-keyed jit
caching.
"""
import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from ..core.tensor import Tensor
from ..core import autograd
from ..core.random import rng_guard, default_generator
from ..jit import bind_tensors


class OffloadTrainStep:
    """K-microbatch accumulation + chunked host-offloaded optimizer.

    Each call runs ONE micro-step (fwd+bwd+accumulate, one fused XLA
    program, grad-accum buffers donated); every `accumulate_steps`-th
    call additionally applies the optimizer chunk-by-chunk and zeroes the
    accumulators. Numerics match a full-batch fused TrainStep: the loss
    is the mean over each micro-batch and the applied gradient is the
    mean over the K micro-gradients.

    param_dtype: optional cast for the DEVICE-resident parameters (e.g.
    "bfloat16"); with a multi_precision optimizer the f32 master rides
    the host-resident state dict, so update precision is unaffected
    (reference amp O2 master-weight semantics).
    """

    def __init__(self, model, loss_fn, optimizer, accumulate_steps=1,
                 param_dtype=None, chunk_bytes=1 << 30):
        self.model = model
        self.loss_fn = loss_fn
        self.optimizer = optimizer
        self.K = int(accumulate_steps)
        named = [(n, p) for n, p in model.named_parameters()
                 if not p.stop_gradient]
        self.params = [p for _, p in named]
        self.buffers = [b for _, b in model.named_buffers() if b is not None]
        if param_dtype is not None:
            cdt = jnp.dtype(param_dtype)
            for p in self.params:
                if jnp.issubdtype(p._value.dtype, jnp.floating):
                    p._value = p._value.astype(cdt)
        dev = jax.devices()[0]
        self._dev_sh = SingleDeviceSharding(dev)
        # decided by platform, not by a caught error: the CPU backend
        # lists a pinned_host memory but cannot compile the cross-space
        # transfer ("No registered implementation for ...
        # annotate_device_placement for Host"), so the CPU suite runs
        # accumulation-only (numerics identical). Any other backend
        # places the states on the host, and one that cannot fails at
        # the first device_put below instead of quietly keeping them
        # in HBM.
        self._offload = dev.platform != "cpu"
        self._host_sh = SingleDeviceSharding(
            dev, memory_kind="pinned_host") if self._offload \
            else self._dev_sh
        # optimizer states (incl. any fp32 master) -> host
        for p in self.params:
            st = optimizer._get_state(p)
            for k, v in st.items():
                st[k] = jax.device_put(jnp.asarray(v), self._host_sh)
        self._acc = [jnp.zeros(p._value.shape, jnp.float32)
                     for p in self.params]
        self._chunks = self._pack_chunks(chunk_bytes)
        self._micro = None
        self._upd_cache = {}
        self._micro_count = 0

    # ---- chunking -------------------------------------------------------
    def _pack_chunks(self, chunk_bytes):
        """Greedy pack consecutive params so param+accum+state bytes stay
        under chunk_bytes; consecutive params follow registration order,
        so each transformer block lands in its own (identical) chunk."""
        chunks, cur, cur_b = [], [], 0
        for i, p in enumerate(self.params):
            n = int(np.prod(p._value.shape))
            st = self.optimizer._states[id(p)]
            b = (n * p._value.dtype.itemsize + n * 4
                 + sum(int(np.prod(np.shape(v))) * 4 for v in st.values()))
            if cur and cur_b + b > chunk_bytes:
                chunks.append(cur)
                cur, cur_b = [], 0
            cur.append(i)
            cur_b += b
        if cur:
            chunks.append(cur)
        return chunks

    # ---- compiled pieces ------------------------------------------------
    def _make_micro(self):
        params, buffers, loss_fn = self.params, self.buffers, self.loss_fn

        def micro(pvals, accs, buf_vals, rng, *batch_vals):
            with autograd.fresh_tape(), bind_tensors(params, pvals), \
                    bind_tensors(buffers, buf_vals), rng_guard(rng):
                batch = [Tensor(v) for v in batch_vals]
                loss = loss_fn(*batch)
                autograd.backward(loss)
                grads = [p.grad._value if p.grad is not None
                         else jnp.zeros_like(p._value) for p in params]
            new_accs = [a + g.astype(jnp.float32)
                        for a, g in zip(accs, grads)]
            return loss._value, new_accs

        return jax.jit(micro, donate_argnums=(1,))

    def _chunk_update_fn(self, idxs):
        """One jitted update per chunk SHAPE (the 24 identical blocks
        compile once). The H2D of the chunk's host-resident states and
        the D2H of the updated states happen IN-GRAPH (in/out shardings
        carry the pinned_host memory kind, `jax.device_put` inside the
        program crosses memory spaces), so a full update round costs
        ~n_chunks dispatches instead of ~n_params*n_state_keys*2
        device_puts — measured 15.1 s -> see BENCH for the fixed number
        on the 1.3B round (the per-put dispatch RTT dominated)."""
        sig = tuple((tuple(self.params[i]._value.shape),
                     str(self.params[i]._value.dtype),
                     tuple(sorted(
                         (k, tuple(np.shape(v)))
                         for k, v in
                         self.optimizer._states[id(self.params[i])].items()))
                     ) for i in idxs)
        fn = self._upd_cache.get(sig)
        if fn is not None:
            return fn
        opt, K = self.optimizer, self.K
        chunk_params = [self.params[i] for i in idxs]
        dev_sh, host_sh = self._dev_sh, self._host_sh

        offload = self._offload

        def upd(pvals, accs, states, lr):
            if offload:
                states = jax.tree_util.tree_map(
                    lambda v: jax.device_put(v, dev_sh), states)
            grads = [a / K for a in accs]
            with autograd.no_grad():
                new_vals, new_states = opt._functional_apply(
                    chunk_params, pvals, grads, states, lr)
            if offload:
                new_states = jax.tree_util.tree_map(
                    lambda v: jax.device_put(v, host_sh), new_states)
            zeroed = [jnp.zeros_like(a) for a in accs]
            return new_vals, new_states, zeroed

        if offload:
            n = len(idxs)
            state_sh = [
                {k: host_sh
                 for k in self.optimizer._states[id(self.params[i])]}
                for i in idxs]
            in_sh = ([dev_sh] * n, [dev_sh] * n, state_sh, dev_sh)
            out_sh = ([dev_sh] * n, state_sh, [dev_sh] * n)
            fn = jax.jit(upd, in_shardings=in_sh, out_shardings=out_sh,
                         donate_argnums=(0, 1, 2))
        else:
            fn = jax.jit(upd, donate_argnums=(0, 1, 2))
        self._upd_cache[sig] = fn
        return fn

    # ---- driver ---------------------------------------------------------
    def _repin(self, st):
        """States mutated OUT-OF-BAND (set_state_dict on checkpoint
        restore) arrive as plain arrays; the jitted chunk update
        declares pinned_host in_shardings, so re-pin anything that lost
        the host memory kind."""
        if not self._offload:
            return st
        out = {}
        for k, v in st.items():
            mk = getattr(getattr(v, "sharding", None), "memory_kind",
                         None)
            out[k] = v if mk == "pinned_host" else \
                jax.device_put(jnp.asarray(v), self._host_sh)
        return out

    def _apply_update(self):
        opt = self.optimizer
        lr = jnp.asarray(opt.get_lr(), jnp.float32)
        for idxs in self._chunks:
            fn = self._chunk_update_fn(idxs)
            pvals = [self.params[i]._value for i in idxs]
            accs = [self._acc[i] for i in idxs]
            states = [self._repin(opt._states[id(self.params[i])])
                      for i in idxs]
            new_vals, new_states, zeroed = fn(pvals, accs, states, lr)
            for i, v, a, st in zip(idxs, new_vals, zeroed, new_states):
                self.params[i]._value = v
                self._acc[i] = a
                opt._states[id(self.params[i])] = st

    def __call__(self, *batch):
        if self._micro is None:
            self._micro = self._make_micro()
        batch_vals = [b._value if isinstance(b, Tensor)
                      else jnp.asarray(b) for b in batch]
        pvals = [p._value for p in self.params]
        buf_vals = [b._value for b in self.buffers]
        rng = default_generator().split()
        loss, self._acc = self._micro(pvals, self._acc, buf_vals, rng,
                                      *batch_vals)
        self._micro_count += 1
        if self._micro_count >= self.K:
            self._micro_count = 0
            self._apply_update()
        return Tensor(loss)
