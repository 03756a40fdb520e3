"""Vectorized mega-fleet engines: the whole epoch as (devices,)-array ops.

``fleet.simulate`` walks a per-device Python loop over Lindley FIFOs —
correct, observable, and capped at a few hundred devices per wall-clock
second. This module turns the epoch into fused array programs over a
*padded ragged layout*: each epoch's per-device arrivals (counts c_d,
max C = counts.max()) become an (n, C) matrix of sorted arrival
offsets, padded past each device's count with a sentinel that sorts
last; the Lindley recursion C_k = max(A_k, C_{k-1}) + s then runs as a
row-wise running max (``lindley_core``), identical elementwise to the
loop's 1-D recursion, so the valid prefix of every row is *bit-equal*
to what the loop computes.

Three engines share that core (``FleetConfig.engine``):

- ``"loop"``   — the original per-device loop (kept in ``fleet.py`` as
  the parity oracle).
- ``"vectorized"`` — pure numpy, one ``lindley_core`` call per epoch.
  Bit-identical to the loop: a single ``uniform(size=counts.sum())``
  draw consumes the world-rng stream exactly like the loop's
  per-device draws (PCG64 doubles are consumed sequentially), the
  padded sort reproduces each device's sorted offsets, and the
  row-major flatten reproduces the loop's device-order metric
  recording. Same seed ⇒ identical latencies, histogram, counters.
- ``"scan"``   — a jitted ``jax.lax.scan`` over epochs
  (``simulate_scan``), float32, with an opt-in ``shard_map`` device
  axis (``FleetConfig.shard``). The trace counts come from the *same*
  trace-rng stream as the host engines (presampled in epoch order) and
  the initial world state from the same world-rng draws, but per-epoch
  world dynamics and arrival offsets draw from a jax PRNG — so
  cross-engine parity is statistical (same physics, same workload,
  different noise realization), not bitwise. Latency percentiles come
  from a fixed log-spaced histogram (512 bins over 1e-4..1e4 s: ~3.7%
  relative resolution); count/SLO/energy accumulators are exact.

f32 time safety: the scan carries ``free_rel`` — each device's FIFO
drain time *relative to the epoch start* — instead of absolute time, so
a 100k-epoch run never hits float32's ~0.06 s resolution at t ~ 1e6 s.
"""
from __future__ import annotations

from typing import Optional, Sequence

import numpy as np

from repro import obs
from repro.core.env import EnvConfig, ProfileTables
from repro.sim.traces import Trace, presample_counts

# latency-histogram shape shared by the scan engine and its summary:
# log-spaced edges, geometric-midpoint percentile readout
_NBINS = 512
_LAT_LO, _LAT_HI = 1e-4, 1e4


def lindley_core(xp, offs, free_at, head_tx_s, tail_s, offloaded,
                 srv_wait):
    """Row-wise Lindley recursion over the padded (n, C) layout.

    ``offs``: per-device sorted arrival times (absolute or
    epoch-relative — the recursion is shift-invariant), padded past each
    device's count with values that sorted last. ``free_at``: (n,) time
    each device's FIFO drains. Returns ``(lat, done)`` both (n, C);
    entries past a device's count are garbage the caller masks out.

    Elementwise identical to the loop engine's 1-D recursion: the
    running max only ever looks left within a row, and padding sits at
    the row's end, so the valid prefix never sees it.
    """
    n, C = offs.shape
    idx = xp.arange(C)
    s = head_tx_s[:, None]
    if xp is np:
        # in-place variant: the identical operations in the identical
        # order (so results stay bit-equal to the loop oracle), buffers
        # reused — at 100k devices the (n, C) temporaries are the
        # epoch's dominant cost
        done = np.maximum(offs, free_at[:, None])
        done -= s * idx[None, :]
        np.maximum.accumulate(done, axis=1, out=done)      # start
        done += s * (idx[None, :] + 1)
        lat = done - offs
        lat += tail_s[:, None]
        np.add(lat, srv_wait, out=lat, where=offloaded[:, None])
        return lat, done
    import jax
    shifted = xp.maximum(offs, free_at[:, None]) - s * idx[None, :]
    start = jax.lax.cummax(shifted, axis=1)
    done = start + s * (idx[None, :] + 1)
    lat = done - offs + tail_s[:, None]
    lat = xp.where(offloaded[:, None], lat + srv_wait, lat)
    return lat, done


def padded_offsets(counts, u, slot_seconds):
    """Pack a flat draw of ``counts.sum()`` uniforms into the padded
    (n, C) layout and sort each row: row d's first ``counts[d]`` entries
    are device d's sorted offsets (boolean-mask assignment fills in
    row-major order, i.e. device order — the same draws the loop engine
    would have pulled per device). Padding is ``2 * slot`` — finite (no
    inf-inf NaN warnings downstream) and past every valid draw, so it
    sorts last. Returns ``(offsets, valid)``."""
    n = counts.shape[0]
    C = max(int(counts.max()), 1)
    col = np.arange(C)
    valid = col[None, :] < counts[:, None]
    pad = np.full((n, C), 2.0 * slot_seconds)
    pad[valid] = u
    pad.sort(axis=1)
    return pad, valid


def numpy_queues(counts, alive, free_at, pr, srv_wait, t_now,
                 slot_seconds, w_rng, metrics, slo_s):
    """One epoch of request flow, vectorized (engine="vectorized").

    Draws the epoch's arrival offsets in ONE ``uniform`` call — PCG64
    consumes doubles sequentially, so this is bitwise the same stream
    state as the loop's per-device draws — then runs ``lindley_core``
    over the padded layout and records metrics in the loop's
    device-major order. Mutates ``free_at`` in place; returns slo_hits.
    """
    total = int(counts.sum())
    if total == 0:
        return 0
    u = w_rng.uniform(0.0, slot_seconds, total)
    pad, valid = padded_offsets(counts, u, slot_seconds)
    pad += t_now          # == t_now + sort(u): the loop's exact values
    offs = pad
    # scalar (classic) or (n,) per-device routed-server wait (cluster):
    # the latter broadcasts as a column over the (n, C) layout
    sw = srv_wait[:, None] if np.ndim(srv_wait) else srv_wait
    lat, done = lindley_core(np, offs, free_at, pr.head_s + pr.tx_s,
                             pr.tail_s, pr.offloaded, sw)
    upd = alive & (counts > 0)
    last = np.take_along_axis(done, np.maximum(counts - 1, 0)[:, None],
                              axis=1)[:, 0]
    free_at[upd] = last[upd]
    sel = valid & alive[:, None]
    lats = lat[sel]
    if lats.size == 0:
        return 0
    n = counts.shape[0]
    energies = np.broadcast_to(pr.energy_j[:, None], lat.shape)[sel]
    devs = np.broadcast_to(np.arange(n)[:, None], lat.shape)[sel]
    metrics.record(lats, energies, device=devs)
    return int(np.sum(lats <= slo_s))


# --------------------------------------------------------------------------
# scan engine
# --------------------------------------------------------------------------

def _hist_percentile(hist, edges, count, q):
    """Latency quantile from the log-binned histogram: the geometric
    midpoint of the first bin whose cumulative count reaches q."""
    if count <= 0:
        return 0.0
    cum = np.cumsum(hist)
    i = int(np.searchsorted(cum, q * count))
    i = min(i, hist.size - 1)
    lo = edges[i - 1] if i > 0 else _LAT_LO / 2
    hi = edges[i] if i < edges.size else _LAT_HI
    return float(np.sqrt(lo * hi))


def simulate_scan(env_cfg: EnvConfig, tables: ProfileTables, policy,
                  trace: Trace, *, n_requests: int = 100_000,
                  seed: int = 0, fleet=None,
                  backend=None,
                  model_ids: Optional[Sequence[int]] = None):
    """The fully-jitted engine: one ``lax.scan`` over epochs, every
    epoch a fused (devices,)-array step (decide → price → padded
    Lindley → accumulate → world dynamics), float32 throughout.

    Workload parity with the host engines: the per-epoch arrival counts
    are presampled from the identical trace-rng stream, and the initial
    world state (bandwidth, transmit power) from the identical
    world-rng draws; only per-epoch dynamics noise and intra-slot
    arrival offsets come from a jax PRNG. Stationary worlds only — a
    drift ``schedule``, ``online`` adaptation, and the ExecuteBackend
    need host round-trips and raise upstream in ``fleet.simulate``.

    ``fleet.shard=True`` runs the scan under ``shard_map`` over every
    visible jax device (fleet axis sharded, scalar reductions psum'd).
    Per-device noise keys fold in the shard index, and the unsharded
    path folds index 0, so a 1-device mesh is bit-identical to
    ``shard=False``. Requires a per-device-decomposable policy (any
    static registry policy); trainable nets read the whole fleet's
    observation and are rejected.

    Returns a ``fleet.SimResult`` whose ``metrics`` holds only the drop
    counter — per-request arrays never leave the device; ``summary``
    is built from in-scan accumulators (percentiles from the log-binned
    histogram, everything else exact).
    """
    import jax
    import jax.numpy as jnp

    from repro.core import energy as en
    from repro.core import pricing
    from repro.core.controller import measured_state
    from repro.sim.fleet import FleetConfig, SimResult
    from repro.sim.metrics import EpochLog, FleetMetrics

    fleet = fleet if fleet is not None else FleetConfig()
    cfg = env_cfg
    n = cfg.n_uavs
    lp, pw = cfg.latency, cfg.power
    slot = float(cfg.slot_seconds)
    if getattr(policy, "trainable", False) and fleet.shard:
        raise ValueError(
            "engine='scan' with shard=True needs a per-device-"
            "decomposable policy; trainable nets read the whole fleet's "
            "observation and cannot act on a device shard")

    if model_ids is None:
        model_ids = np.arange(n, dtype=np.int32) % tables.n_models
    model_ids = np.asarray(model_ids, dtype=np.int32)

    # identical seeding scheme to the host engines
    ss = np.random.SeedSequence(seed)
    s_trace, s_world = ss.spawn(2)
    t_rng = np.random.default_rng(s_trace)
    w_rng = np.random.default_rng(s_world)
    bw0 = w_rng.uniform(lp.bw_min_bps, lp.bw_max_bps, n)
    ptx0 = w_rng.uniform(pw.p_tx_min, pw.p_tx_max, n)

    with obs.span("fleet.scan.presample"):
        counts = presample_counts(trace, t_rng, n, slot, n_requests,
                                  fleet.max_epochs)
    T = counts.shape[0]
    if T == 0:
        raise ValueError("engine='scan' presampled zero epochs; "
                         "n_requests and max_epochs must both be > 0")
    C = max(int(counts.max()), 1)
    served = int(counts.sum())

    norm_rps = fleet.load_norm_rps or (
        cfg.peak_rps if cfg.peak_rps > 0 else max(2.0 * trace.mean_rps,
                                                  1e-9))
    M, V, K = tables.n_models, tables.n_versions, tables.n_cuts
    edges = np.geomspace(_LAT_LO, _LAT_HI, _NBINS - 1)
    edges_j = jnp.asarray(edges, jnp.float32)

    # sharding: pad the fleet axis to a multiple of the mesh size with
    # dead devices (battery 0, zero arrivals — they price, but serve,
    # drop, and drain nothing)
    ndev = len(jax.devices()) if fleet.shard else 1
    pad_n = (-n) % ndev
    npad = n + pad_n
    if pad_n:
        counts = np.pad(counts, ((0, 0), (0, pad_n)))
        model_ids = np.pad(model_ids, (0, pad_n))
        bw0 = np.pad(bw0, (0, pad_n), constant_values=lp.bw_min_bps)
        ptx0 = np.pad(ptx0, (0, pad_n), constant_values=pw.p_tx_min)
    battery0 = np.where(np.arange(npad) < n, pw.battery_j, 0.0)

    def epoch_step(mids, shard_idx, carry, inp):
        (battery, bw, p_tx, activity, side_q, backlog_s, free_rel,
         obs_rate, key, acc) = carry
        counts_t, epoch = inp
        cf = counts_t.astype(jnp.float32)
        key, k_epoch = jax.random.split(key)
        k_loc = jax.random.fold_in(k_epoch, shard_idx)
        k_pol, k_arr, k_bw, k_ptx, k_act = jax.random.split(k_loc, 5)
        k_q = jax.random.fold_in(k_epoch, _NBINS)  # replicated scalar draw

        def g(x):                      # global reduction across the mesh
            return jax.lax.psum(x, "d") if fleet.shard else x

        alive = battery > 0.0
        queue_jobs = side_q + backlog_s / lp.job_service_s
        srv_wait = queue_jobs * lp.job_service_s
        obs_queue = jnp.minimum(queue_jobs, fleet.queue_obs_clip)
        load = jnp.clip(obs_rate / norm_rps, 0.0, 1.0)

        # 1) decide from measured state (same sensors as the host loop)
        state = measured_state(cfg, tables, battery_j=battery,
                               bandwidth=bw, p_tx=p_tx,
                               queue_jobs=obs_queue, load=load,
                               model_id=mids, activity=activity, t=epoch)
        actions = policy.act(state, k_pol)

        # 2) price under the same view the AnalyticalBackend builds
        view = pricing.StateView(model_id=mids, bandwidth=bw, p_tx=p_tx,
                                 queue=0.0, load=0.0)
        pr = pricing.price_actions(cfg, tables, view, actions, xp=jnp)

        # 3) padded-ragged Lindley in epoch-relative time
        u = jax.random.uniform(k_arr, (mids.shape[0], C), maxval=slot)
        col = jnp.arange(C)
        validm = col[None, :] < counts_t[:, None]
        offs = jnp.sort(jnp.where(validm, u, 2.0 * slot), axis=1)
        lat, done = lindley_core(jnp, offs, free_rel,
                                 pr.head_s + pr.tx_s, pr.tail_s,
                                 pr.offloaded, srv_wait)
        upd = alive & (counts_t > 0)
        last = jnp.take_along_axis(
            done, jnp.maximum(counts_t - 1, 0)[:, None], axis=1)[:, 0]
        free_rel = jnp.where(upd, last, free_rel)
        # shift the time origin to the next epoch; anything already
        # drained clamps to "free now" (f32-safe over any horizon)
        free_rel = jnp.maximum(free_rel - slot, 0.0)

        sel = validm & alive[:, None]
        slo_hits = g(jnp.sum(sel & (lat <= fleet.slo_s)))
        dropped_t = g(jnp.sum(jnp.where(alive, 0, counts_t)))
        count_t = g(jnp.sum(jnp.where(alive, counts_t, 0)))
        lat_sum = g(jnp.sum(jnp.where(sel, lat, 0.0)))
        lat_max = g(jnp.max(jnp.where(sel, lat, -jnp.inf)))
        e_sum = g(jnp.sum(jnp.where(alive, cf * pr.energy_j, 0.0)))
        bins = jnp.clip(jnp.searchsorted(edges_j, lat), 0, _NBINS - 1)
        hist_lat_t = g(jnp.zeros(_NBINS, jnp.int32)
                       .at[bins.ravel()].add(sel.ravel()
                                             .astype(jnp.int32)))
        flat = (mids * V + actions[:, 0]) * K + actions[:, 1]
        hist_sel_t = g(jnp.zeros(M * V * K, jnp.int32)
                       .at[flat].add(jnp.where(alive, counts_t, 0)
                                     .astype(jnp.int32)))
        tail_in = g(jnp.sum(jnp.where(upd & pr.offloaded,
                                      cf * pr.tail_s, 0.0)))

        # 4) world dynamics (mirrors the host loop, jax noise)
        kin = en.kinetic_power(pw, activity[:, 0], activity[:, 1],
                               activity[:, 2])
        drain = jnp.where(alive, kin * slot + cf * pr.energy_j, 0.0)
        battery = jnp.maximum(battery - drain, 0.0)
        nloc = bw.shape[0]
        bw = jnp.clip(bw * jnp.exp(jax.random.normal(k_bw, (nloc,))
                                   * 0.15), lp.bw_min_bps, lp.bw_max_bps)
        p_tx = jnp.clip(p_tx + jax.random.normal(k_ptx, (nloc,)) * 0.05,
                        pw.p_tx_min, pw.p_tx_max)
        activity = jnp.clip(activity
                            + jax.random.normal(k_act, (nloc, 3))
                            * cfg.activity_jitter, 0.0, 1.0)
        activity = activity / jnp.maximum(
            activity.sum(-1, keepdims=True), 1.0)
        side_q = jnp.maximum(
            side_q + jax.random.poisson(k_q, cfg.queue_arrival_rate)
            .astype(jnp.float32) - cfg.queue_service_per_slot, 0.0)
        backlog_s = jnp.maximum(backlog_s + tail_in - slot, 0.0)
        obs_rate = (1.0 - fleet.ewma) * obs_rate + fleet.ewma * cf / slot

        acc = {"count": acc["count"] + count_t - dropped_t,
               "dropped": acc["dropped"] + dropped_t,
               "slo_hits": acc["slo_hits"] + slo_hits,
               "lat_sum": acc["lat_sum"] + lat_sum,
               "lat_max": jnp.maximum(acc["lat_max"], lat_max),
               "e_sum": acc["e_sum"] + e_sum,
               "hist_lat": acc["hist_lat"] + hist_lat_t,
               "hist_sel": acc["hist_sel"] + hist_sel_t}
        carry = (battery, bw, p_tx, activity, side_q, backlog_s,
                 free_rel, obs_rate, key, acc)
        # per-epoch stacked outputs: O(1) scalars only (the scan-carry
        # rule — DESIGN §13). Always emitted, timeline on or off, so the
        # compiled graph is identical either way; the timeline is pure
        # host-side extraction below.
        ys = (queue_jobs, backlog_s, dropped_t, slo_hits,
              g(jnp.sum(alive.astype(jnp.int32))),
              count_t, lat_sum, lat_max, e_sum)
        return carry, ys

    def run(counts_all, epochs_all, mids, bat0, bwi, pti, shard_idx):
        nloc = mids.shape[0]
        acc0 = {"count": jnp.int32(0), "dropped": jnp.int32(0),
                "slo_hits": jnp.int32(0), "lat_sum": jnp.float32(0.0),
                "lat_max": jnp.float32(-jnp.inf),
                "e_sum": jnp.float32(0.0),
                "hist_lat": jnp.zeros(_NBINS, jnp.int32),
                "hist_sel": jnp.zeros(M * V * K, jnp.int32)}
        carry0 = (bat0.astype(jnp.float32), bwi.astype(jnp.float32),
                  pti.astype(jnp.float32),
                  jnp.tile(jnp.asarray(cfg.activity, jnp.float32)[None],
                           (nloc, 1)),
                  jnp.float32(0.0), jnp.float32(0.0),
                  jnp.zeros(nloc, jnp.float32),
                  jnp.full(nloc, trace.mean_rps, jnp.float32),
                  jax.random.key(seed), acc0)
        carry, ys = jax.lax.scan(
            lambda c, x: epoch_step(mids, shard_idx, c, x),
            carry0, (counts_all, epochs_all))
        return carry[-1], ys

    xs = (jnp.asarray(counts.T, jnp.int32).T,  # (T, npad) int32
          jnp.arange(T, dtype=jnp.int32))
    mids_j = jnp.asarray(model_ids)
    args = (xs[0], xs[1], mids_j, jnp.asarray(battery0, jnp.float32),
            jnp.asarray(bw0, jnp.float32), jnp.asarray(ptx0, jnp.float32))

    with obs.span("fleet.scan", epochs=T, devices=n, shard=fleet.shard):
        if fleet.shard:
            from jax.sharding import Mesh
            from jax.sharding import PartitionSpec as P
            mesh = Mesh(np.asarray(jax.devices()), ("d",))
            sharded = jax.shard_map(
                lambda c, e, m, b, w, p: run(
                    c, e, m, b, w, p, jax.lax.axis_index("d")),
                mesh=mesh,
                in_specs=(P(None, "d"), P(), P("d"), P("d"), P("d"),
                          P("d")),
                out_specs=(P(), (P(),) * 9),
                # accumulators are psum'd every epoch (replicated by
                # construction); skip the conservative replication check
                check_vma=False)
            acc, ys = jax.jit(sharded)(*args)
        else:
            acc, ys = jax.jit(run, static_argnums=(6,))(*args, 0)
        mesh_devices = len(acc["count"].sharding.device_set)
        acc = jax.tree.map(np.asarray, acc)
        ys = jax.tree.map(np.asarray, ys)

    count = int(acc["count"])
    dropped = int(acc["dropped"])
    slo_hits = int(acc["slo_hits"])
    duration = T * slot
    hist = acc["hist_lat"]
    total = count + dropped
    summary = {
        "count": float(count), "unit": "s",
        "mean": float(acc["lat_sum"]) / count if count else 0.0,
        "p50": _hist_percentile(hist, edges, count, 0.50),
        "p95": _hist_percentile(hist, edges, count, 0.95),
        "p99": _hist_percentile(hist, edges, count, 0.99),
        "max": float(acc["lat_max"]) if count else 0.0,
        "slo": float(fleet.slo_s),
        "slo_attainment": slo_hits / total if total else float("nan"),
        "goodput": slo_hits / duration if duration else 0.0,
        "dropped": float(dropped),
        "energy_j": float(acc["e_sum"]),
        "energy_per_request_j": float(acc["e_sum"]) / count if count
        else 0.0,
        "duration_s": duration,
        "epochs": T, "requests": served,
    }

    metrics = FleetMetrics(slo_s=fleet.slo_s)
    metrics.dropped = dropped
    epoch_log = EpochLog(stride=fleet.log_stride, cap=fleet.log_cap)
    (q_jobs, backlog, drop_t, slo_t, alive_t,
     srv_t, lsum_t, lmax_t, e_t) = ys
    if fleet.record_epochs:
        epoch_log.extend_columns(
            epoch=np.arange(T), arrivals=counts[:, :n].sum(axis=1),
            queue_jobs=q_jobs, backlog_s=backlog, dropped=drop_t,
            slo_hits=slo_t, alive=alive_t, regime=np.zeros(T, np.int64))
    tl = None
    if fleet.timeline:
        from repro.obs.slo import SLOConfig
        from repro.obs.timeline import Timeline
        tl = Timeline(slo_s=fleet.slo_s, slot_seconds=slot,
                      stride=fleet.log_stride, engine="scan")
        with obs.span("fleet.timeline"):
            tl.extend_epochs(
                epoch=np.arange(T), arrivals=counts[:, :n].sum(axis=1),
                served=srv_t, dropped=drop_t, slo_hits=slo_t,
                alive=alive_t, queue_jobs=q_jobs, backlog_s=backlog,
                lat_sum=lsum_t, lat_max=lmax_t, energy_j=e_t)
            tl.finalize(SLOConfig(target=fleet.slo_target))
    sel_hist = acc["hist_sel"].astype(np.int64).reshape(M, V, K)
    return SimResult(summary=summary, metrics=metrics,
                     selection_hist=sel_hist, epochs=T, served=served,
                     duration_s=duration, cross_check=None,
                     epoch_log=epoch_log, adaptation=None, timeline=tl,
                     mesh_devices=mesh_devices)
