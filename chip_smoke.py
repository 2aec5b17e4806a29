"""Chip smoke: train -> save -> async save -> kill -> restore on a real TPU.

The quickest proof that the system still starts on the chip. One process
drives the main path once through the entry points a user calls
(``CheckpointManager.save`` / ``async_save`` / ``restore``, hence
``Snapshot.take`` / ``async_take`` / ``restore``; the flagship
transformer's ``init_params`` and a jitted, donating ``sgd_train_step``)
at the full published GPT-3 6.7B widths the model implements (Brown et
al. 2020, table 2.1): d_model 4096, 32 heads of 128, d_ff 16384, vocab
50257, sequence 2048, float32 parameters, Pallas flash attention (forward
and both backward kernels at 1024-row tiles) inside the step. Only depth
is cut (``N_LAYERS``); weights are random, made from a seed.

    python3 chip_smoke.py                  # needs a TPU; fails without one
    python3 chip_smoke.py --cpu-rehearsal  # toy widths on CPU, NOT a chip run

Every phase failure is a non-zero exit; nothing is recorded and carried
on from. The last two lines of stdout are JSON objects: the summary of
what was observed, ending ``"claim": null``, and then — last, with exactly
these keys, because the driver's chip check reads it — ::

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

Every second it prints is a single-run observation on the named device,
not a result. Snapshot payloads go to a scratch directory under the
system temp dir and are removed on the way out.
"""

import argparse
import contextlib
import dataclasses
import faulthandler
import gc
import importlib.metadata
import json
import logging
import os
import shutil
import sys
import tempfile
import time
import warnings

_CHECKOUT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, _CHECKOUT)

import numpy as np  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

from torchsnapshot_tpu import (  # noqa: E402
    CheckpointManager,
    PytreeStateful,
    Snapshot,
    StateDict,
)
from torchsnapshot_tpu.fingerprint import (  # noqa: E402
    fingerprint_device_async,
    fingerprint_host,
    resolve_fingerprints,
)
from torchsnapshot_tpu.models.transformer import (  # noqa: E402
    TransformerConfig,
    forward,
    init_params,
    loss_fn,
    sgd_train_step,
    shard_params,
)
from torchsnapshot_tpu.ops.attention import (  # noqa: E402
    resolve_flash_block,
    resolve_interpret,
)
from torchsnapshot_tpu.ops.transfer import probe_h2d_gbps  # noqa: E402
from torchsnapshot_tpu.parallel.mesh import make_mesh  # noqa: E402
from torchsnapshot_tpu.utils.compile_cache import (  # noqa: E402
    configure_compile_cache,
)

# GPT-3 6.7B (Brown et al. 2020, table 2.1). No width is cut.
_FULL_WIDTHS = dict(
    vocab_size=50257, d_model=4096, n_heads=32, d_ff=16384, max_seq_len=2048
)
# Depth is the cut: the largest at which the whole smoke, compile cache
# cold, is known to fit the chip's HBM with headroom — the async save's
# on-device clones live beside two training steps. On a v5e (16.9e9 B):
# 5 layers peak at 83 % cold; 6 ran once at 92 % with a warm cache, and a
# cold run peaks ~0.6e9 B higher. Time is not the constraint (~110 s cold
# against the 1200 s contract).
N_LAYERS = 5
# Rehearsal only (--cpu-rehearsal): control flow at toy widths and depth.
_TOY_WIDTHS = dict(
    vocab_size=512, d_model=64, n_heads=4, d_ff=256, max_seq_len=32
)
_TOY_LAYERS = 1
# The sharded leg vocab-shards the embedding, and 50257 is odd: padded to
# a multiple of 128 as Megatron-style trainers do before tensor
# parallelism. Every other width stays full.
_SHARDED_VOCAB = 50304

_BATCH = 1
_WARM_STEPS = 3
_RESUME_STEPS = 3  # two of them run while the async save drains
_TIME_LIMIT_S = 1200
_HOST_FALLBACK_MARK = "falling back to host staging"


def say(msg: str) -> None:
    print(f"[smoke] {msg}", flush=True)


@contextlib.contextmanager
def phase(name: str):
    say(f"--- {name}")
    begin = time.monotonic()
    yield
    say(f"--- {name}: {time.monotonic() - begin:.2f} s")


class _LibraryWarnings(logging.Handler):
    """Collects the package's WARNING+ log records: the capture route of
    an async save and a degraded fingerprint path are only ever reported
    there."""

    def __init__(self) -> None:
        super().__init__(level=logging.WARNING)
        self.messages = []

    def emit(self, record: logging.LogRecord) -> None:
        self.messages.append(record.getMessage())

    def drain(self) -> list:
        out, self.messages = self.messages, []
        return out


class _CompileTally:
    """Counts backend compiles and persistent-cache hits/misses through
    jax.monitoring — the evidence for the cache threshold."""

    def __init__(self) -> None:
        self.hits = 0
        self.misses = 0
        self.compile_secs = []
        jax.monitoring.register_event_listener(self._on_event)
        jax.monitoring.register_event_duration_secs_listener(self._on_secs)

    def _on_event(self, name: str, **_) -> None:
        if name == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif name == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def _on_secs(self, name: str, secs: float, **_) -> None:
        if name == "/jax/core/compile/backend_compile_duration":
            self.compile_secs.append(secs)

    def summary(self) -> dict:
        secs = self.compile_secs
        return {
            "cache_hits": self.hits,
            "cache_misses": self.misses,
            "backend_compiles": len(secs),
            "backend_compile_s": round(sum(secs), 2),
            "compiles_under_1s": sum(1 for s in secs if s < 1.0),
            "compile_s_under_1s": round(sum(s for s in secs if s < 1.0), 2),
        }


def _fs_info(path: str) -> str:
    mount, fstype = "", "unknown"
    with open("/proc/mounts") as f:
        for line in f:
            _, mnt, kind = line.split()[:3]
            if (path == mnt or path.startswith(mnt.rstrip("/") + "/")) and len(
                mnt
            ) > len(mount):
                mount, fstype = mnt, kind
    free = shutil.disk_usage(path).free
    return f"{fstype} (mount {mount}), {free / 1e9:.1f} GB free"


def _libtpu_version() -> str:
    try:
        return importlib.metadata.version("libtpu")
    except importlib.metadata.PackageNotFoundError:
        return "not installed"


def _tree_bytes(tree) -> int:
    return sum(leaf.nbytes for leaf in jax.tree.leaves(tree))


def _tokens(cfg: TransformerConfig, step: int, sharding=None) -> jax.Array:
    """The batch of step ``step``: a pure function of the step index, so
    a resumed run sees exactly the data the uninterrupted run saw."""
    tokens = jax.random.randint(
        jax.random.fold_in(jax.random.key(1), step),
        (_BATCH, cfg.max_seq_len),
        0,
        cfg.vocab_size,
    )
    return tokens if sharding is None else jax.device_put(tokens, sharding)


def _app_state(params, step: int) -> dict:
    return {
        "train": PytreeStateful({"params": params}),
        "progress": StateDict(step=step),
    }


def _host_copy(tree):
    return jax.tree.map(lambda x: np.array(x, copy=True), tree)


def _assert_bit_identical(restored, host_copy, what: str) -> None:
    flat, _ = jax.tree_util.tree_flatten_with_path(restored)
    expected = jax.tree.leaves(host_copy)
    assert len(flat) == len(expected), f"{what}: leaf count differs"
    for (path, leaf), want in zip(flat, expected):
        got = np.asarray(leaf)
        name = jax.tree_util.keystr(path)
        assert got.dtype == want.dtype and got.shape == want.shape, (
            f"{what}: {name} is {got.dtype}{got.shape}, "
            f"saved {want.dtype}{want.shape}"
        )
        same = np.array_equal(
            got.reshape(-1).view(np.uint8), want.reshape(-1).view(np.uint8)
        )
        assert same, f"{what}: {name} is not bit-identical"


def _assert_on_platform(tree, platform: str, what: str) -> None:
    for leaf in jax.tree.leaves(tree):
        got = {d.platform for d in leaf.devices()}
        assert got == {platform}, f"{what}: a leaf lives on {got}"


def _reference_agreement(params, cfg: TransformerConfig) -> dict:
    """Flash path vs the einsum reference on one full-length sequence:
    logits, and the gradients that only reach the parameters through the
    attention kernels' backward (layer 0's wq/wk/wv). Both sides run at
    ``highest`` matmul precision (a TPU otherwise multiplies float32 in
    bf16 passes), so the tolerance is tight enough that computing either
    side in a lower precision would fail it."""
    tokens = _tokens(cfg, step=10_000)

    def probe(config):
        def run(p, t):
            grads = jax.grad(loss_fn)(p, t, config)
            attn = grads["layers"][0]["attn"]
            return forward(p, t, config), (attn["wq"], attn["wk"], attn["wv"])

        with jax.default_matmul_precision("highest"):
            return jax.jit(run)(params, tokens)

    flash_logits, flash_grads = probe(cfg)
    ref_logits, ref_grads = probe(dataclasses.replace(cfg, flash_attention=False))
    assert flash_logits.shape == (_BATCH, cfg.max_seq_len, cfg.vocab_size)
    assert bool(jnp.isfinite(flash_logits).all()), "non-finite logits"

    def rel_err(got, want) -> float:
        return float(jnp.linalg.norm(got - want) / jnp.linalg.norm(want))

    logit_err = rel_err(flash_logits, ref_logits)
    logit_max = float(jnp.abs(flash_logits - ref_logits).max())
    grad_errs = [rel_err(f, r) for f, r in zip(flash_grads, ref_grads)]
    say(
        f"flash vs einsum reference: relative logit error {logit_err:.2e} "
        f"(max |dlogit| {logit_max:.2e}), relative grad error wq/wk/wv "
        f"{'/'.join(f'{e:.2e}' for e in grad_errs)}"
    )
    # At float32 the two paths differ by the online softmax's
    # reassociation only (~1e-6 on logits, ~3e-5 on these gradients on a
    # v5e); one bf16 pass anywhere costs ~5e-3, a wrong kernel O(1).
    assert logit_err < 1e-4, f"flash logits off the reference: {logit_err}"
    assert max(grad_errs) < 1e-3, f"flash grads off the reference: {grad_errs}"
    return {"logit_rel_err": logit_err, "grad_rel_err": max(grad_errs)}


def _train_steps(step_fn, params, cfg, first_step: int, n: int):
    """Run ``n`` steps; returns (params, losses). Each step is fenced by
    ``block_until_ready`` and then fetched: the fetch after the fence
    must be free, or the fence returned early."""
    losses = []
    for i in range(first_step, first_step + n):
        begin = time.monotonic()
        params, loss = step_fn(params, _tokens(cfg, i))
        jax.block_until_ready(loss)
        ready = time.monotonic() - begin
        value = float(loss)
        fetch = time.monotonic() - begin - ready
        assert np.isfinite(value), f"step {i}: non-finite loss {value}"
        say(
            f"step {i}: loss {value!r}  ({ready:.3f} s to ready, "
            f"+{fetch * 1e3:.1f} ms to fetch)"
        )
        assert fetch < max(0.05, 0.5 * ready), (
            f"block_until_ready returned {fetch:.3f} s before the loss "
            f"was fetchable: it is not a fence on this platform"
        )
        losses.append(value)
    return params, losses


def _single_chip_leg(cfg, workdir: str, log: _LibraryWarnings) -> dict:
    platform = jax.devices()[0].platform
    facts = {}
    with phase("init"):
        params = init_params(cfg, jax.random.key(0))
        jax.block_until_ready(params)
        param_bytes = _tree_bytes(params)
        say(
            f"parameters: {param_bytes} bytes ({param_bytes / 1e9:.2f} GB) at "
            f"d_model={cfg.d_model} d_ff={cfg.d_ff} vocab={cfg.vocab_size} "
            f"seq={cfg.max_seq_len} heads={cfg.n_heads} layers={cfg.n_layers}"
        )
        facts["param_bytes"] = param_bytes

    with phase("reference agreement (flash vs einsum, one full sequence)"):
        facts.update(_reference_agreement(params, cfg))

    step_fn = jax.jit(
        lambda p, t: sgd_train_step(p, t, cfg), donate_argnums=(0,)
    )
    base = os.path.join(workdir, "run")
    mgr = CheckpointManager(base)
    step = 0

    with phase(f"(a) {_WARM_STEPS} warm steps (first one compiles)"):
        params, warm_losses = _train_steps(
            step_fn, params, cfg, step, _WARM_STEPS
        )
        step += _WARM_STEPS

    with phase("(b) CheckpointManager.save"):
        begin = time.monotonic()
        mgr.save(step, _app_state(params, step))
        took = time.monotonic() - begin
        say(
            f"sync save of step {step}: {took:.2f} s "
            f"({param_bytes / 1e9 / took:.3f} GB/s end to end)"
        )
        facts["sync_save_s"] = round(took, 2)

    with phase("(c) CheckpointManager.async_save inside the loop"):
        params, _ = _train_steps(step_fn, params, cfg, step, 1)
        step += 1
        begin = time.monotonic()
        saved_host = _host_copy(params)
        took = time.monotonic() - begin
        say(
            f"host copy at save time (np.array per leaf): {took:.2f} s "
            f"({param_bytes / 1e9 / took:.3f} GB/s)"
        )
        saved_step = step
        log.drain()
        begin = time.monotonic()
        pending = mgr.async_save(saved_step, _app_state(params, saved_step))
        blocked = time.monotonic() - begin
        route = (
            "host-staging fallback"
            if any(_HOST_FALLBACK_MARK in m for m in log.drain())
            else "device clones"
        )
        say(
            f"async_save of step {saved_step} blocked {blocked:.2f} s; "
            f"capture route: {route}"
        )
        facts["async_blocked_s"] = round(blocked, 2)
        facts["capture_route"] = route
        # Two steps while it drains: they donate the very buffers the
        # save captured.
        params, expected = _train_steps(step_fn, params, cfg, step, 2)
        step += 2
        begin = time.monotonic()
        pending.wait()
        say(f"wait() for the drain: {time.monotonic() - begin:.2f} s more")
        assert mgr.all_steps() == [_WARM_STEPS, saved_step], mgr.all_steps()

    with phase("(c') async_take(stage='device') on a subtree that must fit"):
        small = {
            "pos_embed": params["pos_embed"],
            "final_ln": params["final_ln"],
            "ln1": params["layers"][0]["ln1"],
        }
        small_host = _host_copy(small)
        log.drain()
        begin = time.monotonic()
        pending = Snapshot.async_take(
            os.path.join(workdir, "device-stage"),
            {"m": PytreeStateful(small)},
            stage="device",
        )
        blocked = time.monotonic() - begin
        snap = pending.wait()
        fallbacks = [m for m in log.drain() if _HOST_FALLBACK_MARK in m]
        assert not fallbacks, f"stage='device' fell back: {fallbacks}"
        target = PytreeStateful(jax.tree.map(jnp.zeros_like, small))
        snap.restore({"m": target})
        _assert_bit_identical(target.tree, small_host, "device-staged subtree")
        say(
            f"device-clone route: blocked {blocked:.3f} s for "
            f"{_tree_bytes(small)} bytes, restored bit-identical"
        )
        del small, target

    with phase(f"(d) {_RESUME_STEPS - 2} more step(s): the expected losses"):
        params, more = _train_steps(
            step_fn, params, cfg, step, _RESUME_STEPS - 2
        )
        expected += more
        step += _RESUME_STEPS - 2
        say(f"expected losses after step {saved_step}: {expected}")

    with phase("(e) kill: drop every array, restore into another seed"):
        del params, pending, snap, mgr
        gc.collect()
        live = sum(a.nbytes for a in jax.live_arrays())
        say(f"live device arrays after the drop: {live} bytes")
        assert live < param_bytes // 4, "the old state is still alive"
        template = init_params(cfg, jax.random.key(999))
        target = PytreeStateful({"params": template})
        progress = StateDict(step=-1)
        del template
        begin = time.monotonic()
        restored_step = CheckpointManager(base).restore(
            {"train": target, "progress": progress}
        )
        took = time.monotonic() - begin
        say(
            f"restore resolved step {restored_step}: {took:.2f} s "
            f"({param_bytes / 1e9 / took:.3f} GB/s end to end)"
        )
        facts["restore_s"] = round(took, 2)
        assert restored_step == saved_step == progress["step"], (
            restored_step, saved_step, progress["step"],
        )
        params = target.tree["params"]
        _assert_on_platform(params, platform, "restored state")
        _assert_bit_identical(params, saved_host, "restored state")
        say(
            f"restored leaves are bit-identical to the host copy taken at "
            f"save time and live on {platform}"
        )
        del saved_host, target

    with phase(f"(f) {_RESUME_STEPS} resumed steps"):
        params, resumed = _train_steps(
            step_fn, params, cfg, saved_step, _RESUME_STEPS
        )
        say(f"resumed losses: {resumed}")
        assert resumed == expected, (
            f"resume is not exact: expected {expected}, resumed {resumed}"
        )
        say("resumed losses equal the uninterrupted run's exactly")
        facts["losses"] = warm_losses + expected

    with phase("fingerprints: take(fingerprint=True) -> restore(verify_device=True)"):
        leaves = {
            "bf16": params["layers"][0]["attn"]["wq"].astype(jnp.bfloat16),
            "f32": params["pos_embed"],
        }
        for name, leaf in leaves.items():
            on_device = resolve_fingerprints([fingerprint_device_async(leaf)])[0]
            if isinstance(on_device, Exception):
                raise on_device
            on_host = fingerprint_host(np.asarray(leaf))
            assert on_device == on_host, (
                f"{name}: device fingerprint {on_device} != host {on_host}"
            )
            say(f"{name} {leaf.shape}: device fingerprint == host ({on_host})")
        leaves_host = _host_copy(leaves)
        log.drain()
        snap = Snapshot.take(
            os.path.join(workdir, "fingerprinted"),
            {"m": PytreeStateful(leaves)},
            fingerprint=True,
        )
        target = PytreeStateful(jax.tree.map(jnp.zeros_like, leaves))
        snap.restore({"m": target}, verify_device=True)
        degraded = [
            m for m in log.drain() if "fingerprint" in m or "verify_device" in m
        ]
        assert not degraded, f"fingerprint path degraded: {degraded}"
        _assert_bit_identical(target.tree, leaves_host, "verify_device restore")
        del leaves, target, snap

    with phase("link probe"):
        h2d = probe_h2d_gbps()
        assert h2d is not None, "probe_h2d_gbps found no usable device"
        say(f"H2D probe (32 MiB chunked put, best of 2): {h2d:.2f} GB/s")
        facts["h2d_probe_gbps"] = round(h2d, 2)

    del params
    gc.collect()
    return facts


def _sharded_leg(cfg, workdir: str) -> dict:
    """Four devices: train sharded, take, restore onto a 2-way and a 2x2
    layout, bit-exact."""
    devices = jax.devices()[:4]
    platform = devices[0].platform
    mesh = make_mesh({"dp": 1, "tp": 4}, devices=devices)
    with phase("sharded: init + shard_params over 4 devices"):
        params = shard_params(init_params(cfg, jax.random.key(2)), mesh)
        jax.block_until_ready(params)
        gc.collect()
        param_bytes = _tree_bytes(params)
        say(
            f"sharded parameters: {param_bytes / 1e9:.2f} GB at "
            f"vocab={cfg.vocab_size} layers={cfg.n_layers}, mesh dp=1 x tp=4"
        )
        sharded = [
            leaf
            for leaf in jax.tree.leaves(params)
            if not leaf.sharding.is_fully_replicated
        ]
        # The embedding and six matrices per layer are tp-sharded.
        assert len(sharded) == 6 * cfg.n_layers + 1, len(sharded)
        for leaf in sharded:
            shards = leaf.addressable_shards
            assert len({s.device for s in shards}) == 4, (
                f"{leaf.shape}: shards on {[s.device for s in shards]}"
            )
            assert all(s.data.nbytes * 4 == leaf.nbytes for s in shards)
        say(
            f"each of the {len(sharded)} tp-sharded leaves has a quarter "
            f"on each of four devices"
        )

    def spread(label: str) -> None:
        stats = [d.memory_stats() for d in devices]
        if None in stats:
            say(f"{label}: memory_stats not reported on {platform}")
            return
        in_use = [s["bytes_in_use"] for s in stats]
        say(f"{label}: bytes_in_use per device {in_use}")
        assert max(in_use) <= 1.5 * sum(in_use) / 4, (
            f"{label}: state piled on one device: {in_use}"
        )

    spread("after shard_params")

    with phase("sharded: 2 train steps (einsum attention under the mesh)"):
        batch_sharding = NamedSharding(mesh, P("dp", None))
        step_fn = jax.jit(
            lambda p, t: sgd_train_step(p, t, cfg, mesh), donate_argnums=(0,)
        )
        for i in range(2):
            begin = time.monotonic()
            params, loss = step_fn(params, _tokens(cfg, i, batch_sharding))
            value = float(loss)
            assert np.isfinite(value), f"sharded step {i}: loss {value}"
            say(f"sharded step {i}: loss {value!r} ({time.monotonic() - begin:.2f} s)")

    with phase("sharded: take"):
        saved_host = _host_copy(params)
        path = os.path.join(workdir, "sharded")
        begin = time.monotonic()
        Snapshot.take(path, {"m": PytreeStateful(params)}, replicated=["**"])
        took = time.monotonic() - begin
        say(f"sharded take: {took:.2f} s ({param_bytes / 1e9 / took:.3f} GB/s)")
    shapes = jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), params)
    del params
    gc.collect()

    took_by_layout = {}
    for label, axes, devs in (
        ("2-way", {"dp": 1, "tp": 2}, devices[:2]),
        ("2x2", {"dp": 2, "tp": 2}, devices),
    ):
        with phase(f"sharded: restore onto {label} {axes}"):
            other = make_mesh(axes, devices=devs)
            template = shard_params(
                jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), shapes),
                other,
            )
            want = jax.tree.map(lambda x: x.sharding, template)
            target = PytreeStateful(template)
            del template
            begin = time.monotonic()
            Snapshot(path).restore({"m": target})
            took_by_layout[label] = round(time.monotonic() - begin, 2)
            say(f"restore onto {label}: {took_by_layout[label]:.2f} s")
            for leaf, sharding in zip(
                jax.tree.leaves(target.tree), jax.tree.leaves(want)
            ):
                assert leaf.sharding.is_equivalent_to(sharding, leaf.ndim), (
                    leaf.sharding, sharding,
                )
            _assert_on_platform(target.tree, platform, f"{label} restore")
            _assert_bit_identical(target.tree, saved_host, f"{label} restore")
            say(f"{label}: bit-identical, on the template's layout")
            if label == "2x2":
                spread("after the 2x2 restore")
            del target, want
            gc.collect()
    return {
        "sharded_param_bytes": param_bytes,
        "sharded_take_s": round(took, 2),
        "sharded_restore_s": took_by_layout,
    }


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    parser.add_argument(
        "--cpu-rehearsal",
        action="store_true",
        help="rehearse the control flow on the CPU backend at toy widths; "
        "NOT a chip run, and never taken by the default invocation",
    )
    args = parser.parse_args()
    begin = time.monotonic()
    # A hang must not outlive the contract: dump every thread and exit.
    faulthandler.dump_traceback_later(_TIME_LIMIT_S - 60, exit=True)

    cache_dir = configure_compile_cache(_CHECKOUT)
    tally = _CompileTally()
    backend = jax.default_backend()
    if args.cpu_rehearsal:
        if backend != "cpu":
            print(
                f"chip_smoke: --cpu-rehearsal is for the CPU backend, "
                f"found {backend!r}",
                file=sys.stderr,
            )
            return 1
        say("NOT A CHIP RUN: --cpu-rehearsal, toy widths on the CPU backend")
    elif backend != "tpu":
        print(
            f"chip_smoke: JAX's backend is {backend!r}, not 'tpu' — no "
            f"accelerator, no result (--cpu-rehearsal rehearses the control "
            f"flow on CPU).",
            file=sys.stderr,
        )
        return 1
    on_tpu = backend == "tpu"

    device = jax.devices()[0]
    device_doc = {
        "platform": device.platform,
        "kind": device.device_kind,
        "count": len(jax.devices()),
    }
    say(
        f"platform={device.platform} device_kind={device.device_kind!r} "
        f"device_count={len(jax.devices())}"
    )
    say(
        f"python {sys.version.split()[0]}, jax {jax.__version__}, jaxlib "
        f"{importlib.metadata.version('jaxlib')}, libtpu {_libtpu_version()}"
    )
    placed = (
        "from JAX_COMPILATION_CACHE_DIR"
        if os.environ.get("JAX_COMPILATION_CACHE_DIR")
        else "in-checkout default"
    )
    entries = len(os.listdir(cache_dir)) if os.path.isdir(cache_dir) else 0
    say(f"compile cache: {cache_dir} ({placed}; {entries} entries before this run)")
    interpret = resolve_interpret()
    say(f"Pallas kernels: interpret={interpret}")
    assert interpret is (not on_tpu)
    if on_tpu:
        bytes_limit = device.memory_stats()["bytes_limit"]
        say(f"HBM bytes_limit: {bytes_limit}")

    cfg = TransformerConfig(
        n_layers=N_LAYERS if on_tpu else _TOY_LAYERS,
        flash_attention=True,
        **(_FULL_WIDTHS if on_tpu else _TOY_WIDTHS),
    )
    say(
        f"flash tiles at seq {cfg.max_seq_len}: "
        f"{resolve_flash_block(cfg.max_seq_len)} rows x head dim "
        f"{cfg.d_model // cfg.n_heads}"
    )

    log = _LibraryWarnings()
    logging.getLogger("torchsnapshot_tpu").addHandler(log)
    workdir = tempfile.mkdtemp(prefix="chip-smoke-")
    say(f"snapshot directory: {workdir} on {_fs_info(workdir)}")
    try:
        with warnings.catch_warnings(record=True) as caught:
            warnings.simplefilter("default")
            facts = _single_chip_leg(cfg, workdir, log)
            if len(jax.devices()) >= 4:
                sharded_cfg = dataclasses.replace(
                    cfg,
                    flash_attention=False,
                    vocab_size=_SHARDED_VOCAB if on_tpu else cfg.vocab_size,
                )
                facts.update(_sharded_leg(sharded_cfg, workdir))
            else:
                say("fewer than 4 devices: sharded leg not run")
    finally:
        shutil.rmtree(workdir, ignore_errors=True)
        logging.getLogger("torchsnapshot_tpu").removeHandler(log)

    seen = sorted({f"{w.category.__name__}: {w.message}" for w in caught})
    say(f"python warnings raised during the run: {len(seen)}")
    for line in seen:
        say(f"  warning: {line}")
    leftover = log.drain()
    if leftover:
        say(f"library warnings not consumed by a phase: {leftover}")
    if on_tpu:
        peak = device.memory_stats()["peak_bytes_in_use"]
        say(
            f"peak_bytes_in_use on device 0: {peak} "
            f"({peak / bytes_limit:.0%} of bytes_limit)"
        )
        facts["peak_bytes_in_use"] = peak
    cache = tally.summary()
    wall = round(time.monotonic() - begin, 1)
    say(f"compile tally: {cache}")
    say(f"wall: {wall} s (limit {_TIME_LIMIT_S} s)")
    faulthandler.cancel_dump_traceback_later()
    print(
        json.dumps(
            {
                "chip_run": on_tpu,
                "n_layers": cfg.n_layers,
                **facts,
                "compile_cache": cache,
                "wall_s": wall,
                "claim": None,
            }
        ),
        flush=True,
    )
    # The driver's line: exactly these keys, and nothing after it.
    print(json.dumps({"ok": True, "device": device_doc}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
