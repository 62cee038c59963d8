"""Plain reference of the simulated Lustre cluster: the paper's testbed
(sec. IV: 6 HDD OSTs, 3 clients on 1 GbE) under the five Filebench
personalities of Table II, with the Table I metrics it reports.

A restatement of the calibrated simulator for the output check, written
from its published description and independent of the program's code: the
noise-free response surface, the client-knob factors, the cache-warmth
AR(1) process, the lognormal run and sample noise, the Table I metrics and
the sec. III-F restart downtime. The random draws follow the documented
order on a JAX threefry key (split six ways per run: next key, warmth,
run noise, sample noise, restart, metrics; the metrics key split ten
ways), so a run on the same key gives the same numbers up to float32
rounding.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

MiB = 1024.0 * 1024.0
NUM_OSTS = 6
NUM_CLIENTS = 3
CLIENT_NIC_MBPS = 117.0
HDD_MBPS = 160.0
NET_CAP = NUM_CLIENTS * CLIENT_NIC_MBPS
L_DEFAULT = 4.0                 # log2(1 MiB / 64 KiB)
RUN_SECONDS = 120.0
SAMPLE_PERIOD = 10.0
EVAL_RUN_SECONDS = 1800.0
N_SAMPLES = max(2, int(RUN_SECONDS / SAMPLE_PERIOD))

# Table II personalities: base MB/s, striping exponent and contention,
# stripe-size optimum / width / amplitude, I/O size KiB, write share,
# metadata rate, cache base, noise sigma, stripe gate, gate width, cache
# sensitivity
FIELDS = ("base_mbps", "gamma", "beta", "l_opt", "l_width", "s_amp",
          "io_kib", "write_frac", "meta_rate", "cache_base", "noise_sigma",
          "l_gate", "gate_width", "cache_kappa")
WORKLOADS = {
    "file_server": (62.0, 0.10, 0.15, 1.0, 3.5, 0.70, 16.0, 0.55, 0.90,
                    0.35, 0.18, -10.0, 0.8, 0.50),
    "video_server": (98.0, 0.25, 0.025, 8.0, 4.5, 0.30, 512.0, 0.15, 0.10,
                     0.55, 0.10, 4.0, 1.0, 0.35),
    "seq_write": (88.0, 0.68, 0.015, 6.0, 3.0, 0.55, 1024.0, 1.00, 0.05,
                  0.10, 0.12, 5.0, 0.6, 0.15),
    "seq_read": (105.0, 0.30, 0.040, 7.0, 4.0, 0.50, 1024.0, 0.00, 0.05,
                 0.60, 0.10, 4.5, 0.8, 0.45),
    "random_rw": (45.0, 0.30, 0.060, 2.0, 4.0, 0.55, 8.0, 0.50, 0.15,
                  0.25, 0.14, -10.0, 0.8, 0.35),
}

# Table I metrics and the two objectives, in state order, with the
# normalization bounds of sec. II-B-3
METRICS = (
    ("cur_dirty_bytes", 0.0, 512 * MiB),
    ("cur_grant_bytes", 0.0, 2048 * MiB),
    ("read_rpcs_in_flight", 0.0, 256.0),
    ("write_rpcs_in_flight", 0.0, 256.0),
    ("pending_read_pages", 0.0, 65536.0),
    ("pending_write_pages", 0.0, 65536.0),
    ("cache_hit_ratio", 0.0, 1.0),
    ("cpu_usage_idle", 0.0, 100.0),
    ("cpu_usage_iowait", 0.0, 100.0),
    ("ram_used_percent", 0.0, 100.0),
    ("throughput", 0.0, 400.0),
    ("iops", 0.0, 60000.0),
)
METRIC_NAMES = tuple(m[0] for m in METRICS)


def workload_params(name: str) -> jnp.ndarray:
    return jnp.asarray(WORKLOADS[name], jnp.float32)


def bounds() -> tuple:
    lo = np.array([m[1] for m in METRICS], np.float32)
    hi = np.array([m[2] for m in METRICS], np.float32)
    return lo, hi - lo


def init_state(seed: int, m: int) -> tuple:
    """(key, warmth, last decoded values): no config applied yet."""
    return (jax.random.PRNGKey(seed), jnp.float32(0.5),
            jnp.full((m,), jnp.nan, jnp.float32))


def mean_perf(p, knobs, dec):
    """Noise-free surface for one decoded configuration. ``p`` holds the
    workload's fields in ``FIELDS`` order, ``dec`` maps knob name to its
    decoded {"value", "log2"}."""
    w = dict(zip(FIELDS, [p[i] for i in range(len(FIELDS))]))
    sc = dec["stripe_count"]["value"]
    l = dec["stripe_size"]["log2"] - 16.0

    par = sc ** w["gamma"] * jnp.exp(-w["beta"] * (sc - 1.0))
    r_gate = 1.0 / (1.0 + jnp.exp(-(l - w["l_gate"]) / w["gate_width"]))
    p_eff = jnp.where(par >= 1.0, 1.0 + (par - 1.0) * r_gate, par)

    def s_raw(ll):
        return 1.0 + w["s_amp"] * (1.0 - ((ll - w["l_opt"]) / w["l_width"])
                                   ** 2)

    s = jnp.maximum(0.4, s_raw(l)) / jnp.maximum(0.4, s_raw(L_DEFAULT))
    x = jnp.maximum(0.6, 1.0 - 0.03 * jnp.maximum(0.0, sc - 1.0)
                    * jnp.maximum(0.0, l - 8.0))
    t = w["base_mbps"] * p_eff * s * x

    if "service_threads" in knobs:
        lg_th = dec["service_threads"]["log2"]
        t = t * (0.75 + 0.33 * jnp.exp(-((lg_th - 7.0) / 3.0) ** 2))
    if "max_rpcs_in_flight" in knobs:
        rif = dec["max_rpcs_in_flight"]["value"]
        lg_rif = dec["max_rpcs_in_flight"]["log2"]
        per_ost = rif / jnp.maximum(sc, 1.0)
        conc = per_ost / (per_ost + 2.0)
        over = 1.0 - 0.03 * w["meta_rate"] * jnp.maximum(0.0, lg_rif - 5.0)
        t = t * conc / (8.0 / 10.0) * jnp.maximum(over, 0.7)
    if "max_pages_per_rpc" in knobs:
        lg_pg = dec["max_pages_per_rpc"]["log2"]
        lr_opt = jnp.clip(w["l_opt"], 0.0, 4.0)

        def rpc_resp(lr):
            return 1.0 + 0.10 * (1.0 - ((lr - lr_opt) / 4.0) ** 2)

        t = t * rpc_resp(jnp.minimum(lg_pg - 4.0, l)) \
            / rpc_resp(jnp.minimum(4.0, l))
    if "max_dirty_mb" in knobs:
        dirty = dec["max_dirty_mb"]["value"]
        lg_dirty = dec["max_dirty_mb"]["log2"]
        h = 1.0 - jnp.exp(-dirty / 24.0)
        h0 = 1.0 - np.exp(-32.0 / 24.0)
        burst = 1.0 - 0.02 * jnp.maximum(0.0, lg_dirty - 9.0)
        t = t * ((1.0 - w["write_frac"]) + w["write_frac"] * h / h0) * burst
    if "read_ahead_mb" in knobs:
        ra = dec["read_ahead_mb"]["value"]
        lg_ra = dec["read_ahead_mb"]["log2"]
        seq = jnp.clip(jnp.log2(w["io_kib"] / 8.0) / 7.0, 0.0, 1.0)
        rf = 1.0 - w["write_frac"]
        h = 1.0 - jnp.exp(-ra / 48.0)
        h0 = 1.0 - np.exp(-64.0 / 48.0)
        gain = 0.25 * rf * seq * (h / h0 - 1.0)
        waste = 0.12 * rf * (1.0 - seq) * jnp.clip((lg_ra - 6.0) / 4.0,
                                                   0.0, 1.0)
        t = t * (1.0 + gain - waste)
    if "checksums" in knobs:
        ck_on = dec["checksums"]["value"] >= 0.5
        t = t * jnp.where(ck_on, 1.0, 1.04 + 0.06 * w["write_frac"])

    t = jnp.minimum(jnp.minimum(t, NET_CAP * 0.95), sc * HDD_MBPS * 1.05)
    amp = 1.0 + 0.6 * jnp.maximum(0.0, L_DEFAULT - l) / L_DEFAULT
    iops = t * 1024.0 / w["io_kib"] * amp
    return w, t, iops, t / NET_CAP, l, sc


def step(p, knobs, dfs_mask, state, dec, values, eval_run: bool):
    """One workload run under a decoded configuration. Returns the new
    state, the 12 metrics (windowed means, state order) and the restart
    seconds."""
    key, warmth0, last_values = state
    changed = values != last_values          # NaN: the first apply changes
    changed_any = jnp.any(changed)
    dfs_changed = jnp.any(changed & dfs_mask)
    key, k_w, k_run, k_samp, k_restart, k_metrics = jax.random.split(key, 6)

    warmth = jnp.where(changed_any, warmth0 * 0.4, warmth0)
    warmth = 0.6 * warmth + 0.4 * jax.random.uniform(k_w)
    warmth_eff = jnp.float32(0.5) if eval_run else warmth

    w, t, iops, util, l, sc = mean_perf(p, knobs, dec)
    run_len = EVAL_RUN_SECONDS if eval_run else RUN_SECONDS
    cache_factor = jnp.exp(w["cache_kappa"] * (warmth_eff - 0.5))
    het = 1.4 - 0.8 * jnp.minimum(1.0, util)
    sigma = w["noise_sigma"] * het * np.float32(np.sqrt(RUN_SECONDS
                                                        / run_len))
    run_factor = cache_factor * jnp.exp(sigma * jax.random.normal(k_run))
    sample_factor = jnp.exp((w["noise_sigma"] / 2.0)
                            * jax.random.normal(k_samp, (N_SAMPLES,)))
    tput = t * run_factor * sample_factor
    iops_s = iops * run_factor * sample_factor

    ks = jax.random.split(k_metrics, 10)

    def jitter(v, k, s=0.05):
        return v * jnp.exp(s * jax.random.normal(k, (N_SAMPLES,)))

    rpc_mb = jnp.minimum(jnp.exp2(l - 4.0), 4.0)
    latency = 0.05 * (1.0 + 3.0 * util ** 2)
    write_mb = tput * w["write_frac"]
    read_mb = tput - write_mb
    cur_dirty = jitter(write_mb * 2.0 * MiB, ks[0])
    cur_grant = jitter((sc * 32.0 + write_mb) * MiB, ks[1])
    read_rpcs = jitter(read_mb / jnp.maximum(rpc_mb, 1e-3) * latency, ks[2])
    write_rpcs = jitter(write_mb / jnp.maximum(rpc_mb, 1e-3) * latency,
                        ks[3])
    pend_r = jitter((read_mb / 4.0) * 256.0 * util ** 2, ks[4])
    pend_w = jitter((write_mb / 4.0) * 256.0 * util ** 2, ks[5])
    cache_hit = jnp.clip(
        w["cache_base"] + 0.45 * (warmth_eff - 0.5) + 0.03 * (l - L_DEFAULT)
        - 0.2 * util + 0.02 * jax.random.normal(ks[6], (N_SAMPLES,)),
        0.0, 1.0)
    cpu_idle = jnp.clip(
        100.0 - 55.0 * w["meta_rate"] - 25.0 * util
        + 2.0 * jax.random.normal(ks[7], (N_SAMPLES,)), 0.0, 100.0)
    iowait = jnp.clip(
        35.0 * w["meta_rate"] * (0.5 + util) + 8.0 * util
        + 1.5 * jax.random.normal(ks[8], (N_SAMPLES,)), 0.0, 100.0)
    ram = jnp.clip(
        28.0 + 40.0 * util + write_mb * 2.0 / (16.0 * 1024.0) * 100.0
        + 1.5 * jax.random.normal(ks[9], (N_SAMPLES,)), 0.0, 100.0)

    if "max_rpcs_in_flight" in knobs:
        cap = dec["max_rpcs_in_flight"]["value"] * jnp.maximum(sc, 1.0)
        pend_r = pend_r + jnp.maximum(0.0, read_rpcs - cap) * 256.0
        pend_w = pend_w + jnp.maximum(0.0, write_rpcs - cap) * 256.0
        read_rpcs = jnp.minimum(read_rpcs, cap)
        write_rpcs = jnp.minimum(write_rpcs, cap)
    if "max_dirty_mb" in knobs:
        cap = dec["max_dirty_mb"]["value"] * MiB
        cur_dirty = jnp.minimum(cur_dirty, cap)
        cur_grant = jnp.minimum(cur_grant, 2.0 * cap + 32.0 * MiB)
    if "read_ahead_mb" in knobs:
        ra = dec["read_ahead_mb"]["value"]
        seq = jnp.clip(jnp.log2(w["io_kib"] / 8.0) / 7.0, 0.0, 1.0)
        h = 1.0 - jnp.exp(-ra / 48.0)
        h0 = 1.0 - np.exp(-64.0 / 48.0)
        shift = 0.10 * (1.0 - w["write_frac"]) * seq * (h / h0 - 1.0)
        cache_hit = jnp.clip(cache_hit + shift, 0.0, 1.0)
    if "checksums" in knobs:
        ck_on = dec["checksums"]["value"] >= 0.5
        cpu_idle = jnp.where(ck_on, jnp.clip(cpu_idle - 8.0 * util, 0.0,
                                             100.0), cpu_idle)

    def smean(x):  # left-to-right sum of the run's samples
        acc = x[0]
        for i in range(1, N_SAMPLES):
            acc = acc + x[i]
        return acc / N_SAMPLES

    metrics = jnp.stack([
        smean(cur_dirty), smean(cur_grant), smean(read_rpcs),
        smean(write_rpcs), smean(pend_r), smean(pend_w), smean(cache_hit),
        smean(cpu_idle), smean(iowait), smean(ram), smean(tput),
        smean(iops_s)]).astype(jnp.float32)
    u = jax.random.uniform(k_restart, minval=12.0, maxval=20.0)
    cost = jnp.where(changed_any, u + jnp.where(dfs_changed, 30.0, 0.0), 0.0)
    return (key, warmth, values), metrics, cost
