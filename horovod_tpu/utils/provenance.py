"""One provenance schema for every durable artifact (docs/alerts.md).

The history plane's run manifest (utils/history.py) stamps this block
{unix_ms, device_kind, device_count, platform, git_sha,
config_fingerprint, mesh, label} so ``tools/hvd_replay.py --diff`` can
attribute any two runs by one field set. This module is that block's
single definition.

Every field is best-effort: a provenance stamp must never kill the
artifact it exists to describe (no git binary in the deploy image, no
jax on a tooling host, an unpicklable config — each just leaves its
field absent).
"""

import hashlib
import os
import subprocess

from . import metrics as hvd_metrics

PROVENANCE_FIELDS = ("unix_ms", "device_kind", "device_count",
                     "platform", "git_sha", "config_fingerprint",
                     "mesh", "label")


def git_sha(cwd=None):
    """Short git sha of the checkout containing ``cwd`` (default: this
    repo), or None outside a checkout / without a git binary."""
    if cwd is None:
        cwd = os.path.dirname(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))))
    try:
        sha = subprocess.run(
            ["git", "rev-parse", "--short", "HEAD"], cwd=cwd,
            capture_output=True, text=True, timeout=10).stdout.strip()
        return sha or None
    # hvdlint: disable=HVD006(no git binary / not a checkout in the deploy image; sha simply absent from provenance)
    except Exception:  # noqa: BLE001 — provenance is best-effort
        return None


def config_fingerprint(cfg):
    """Truncated sha256 of ``repr(cfg)`` — a config identity, not a
    secret. The dataclass repr carries every field incl. overrides, so
    two runs fingerprint equal iff their configs were equal."""
    return hashlib.sha256(repr(cfg).encode()).hexdigest()[:12]


def provenance_stamp(device_count=None, config=None, label=None,
                     mesh=None, git_cwd=None):
    """The shared provenance block: git sha, device kind/count,
    platform, config fingerprint, wall-clock ms and an optional run
    label (``HVD_RUN_LABEL`` when ``label`` is None) — plus the mesh
    layout ({axis: size}) when the caller has one. Pure dict of JSON
    scalars; absent fields are omitted, never None."""
    prov = {"unix_ms": hvd_metrics.shared_clock().epoch_us() // 1000}
    try:
        import jax
        dev = jax.devices()[0]
        prov["device_kind"] = getattr(dev, "device_kind", "")
        prov["platform"] = dev.platform
        prov["device_count"] = (jax.device_count() if device_count is None
                                else int(device_count))
    # hvdlint: disable=HVD006(provenance stamps artifacts from tooling hosts without a usable jax backend; device fields simply absent)
    except Exception:  # noqa: BLE001 — provenance is best-effort
        if device_count is not None:
            prov["device_count"] = int(device_count)
    sha = git_sha(cwd=git_cwd)
    if sha:
        prov["git_sha"] = sha
    if config is not None:
        try:
            prov["config_fingerprint"] = config_fingerprint(config)
        # hvdlint: disable=HVD006(an un-reprable config leaves the fingerprint absent; the stamp must never kill the artifact)
        except Exception:  # noqa: BLE001 — provenance is best-effort
            pass
    if mesh:
        try:
            prov["mesh"] = {str(k): int(v) for k, v in dict(mesh).items()}
        # hvdlint: disable=HVD006(a non-dict mesh spec leaves the field absent; the stamp must never kill the artifact)
        except Exception:  # noqa: BLE001 — provenance is best-effort
            pass
    if label is None:
        label = os.environ.get("HVD_RUN_LABEL")
    if label:
        prov["label"] = str(label)
    return prov


def provenance_diff(a, b):
    """Field-by-field comparison of two provenance blocks -> list of
    ``(field, a_value, b_value)`` rows for every field present in
    either (``unix_ms`` always differs between runs and is included —
    the caller decides whether to show it)."""
    rows = []
    keys = [f for f in PROVENANCE_FIELDS if f in (a or {}) or f in (b or {})]
    for extra in sorted(set(a or {}) | set(b or {})):
        if extra not in keys:
            keys.append(extra)
    for key in keys:
        va, vb = (a or {}).get(key), (b or {}).get(key)
        rows.append((key, va, vb))
    return rows
