"""Plan objects: the emitted, persisted result of a planner run.

A plan is the FULL resolved config the search settled on — the
`zero_optimization.schedule` knobs, activation-checkpointing policy,
offload tier + buffer counts, quantization recipe, and the per-kernel
block geometries — persisted per (device kind, model shape). `ds_plan`
writes these,
`ds_report --json` surfaces the newest fingerprint, and the engine
consumes one through the validated ``"planner"`` config block
(`runtime/config.py:parse_planner_block`).
"""

import hashlib
import json
import os

PLAN_VERSION = 1
PLAN_CACHE_ENV = "DS_PLAN_CACHE"
_DEFAULT_CACHE = os.path.join("~", ".cache", "deeperspeed_tpu", "plans")


def plan_cache_dir(cache_dir=None):
    return os.path.expanduser(
        cache_dir or os.environ.get(PLAN_CACHE_ENV) or _DEFAULT_CACHE)


def _slug(text):
    return "".join(c if c.isalnum() or c in "-._" else "-"
                   for c in str(text)) or "unknown"


def plan_fingerprint(payload):
    """Short content hash over the canonical payload (fingerprint field
    excluded, so re-fingerprinting a loaded plan is stable)."""
    body = {k: v for k, v in payload.items() if k != "fingerprint"}
    blob = json.dumps(body, sort_keys=True, default=str).encode()
    return hashlib.sha256(blob).hexdigest()[:12]


class Plan:
    """Thin, dict-backed wrapper; `payload` is exactly the JSON file."""

    def __init__(self, payload):
        self.payload = dict(payload)
        self.payload.setdefault("version", PLAN_VERSION)
        self.payload["fingerprint"] = plan_fingerprint(self.payload)

    @property
    def fingerprint(self):
        return self.payload["fingerprint"]

    @property
    def device_kind(self):
        return self.payload.get("device_kind", "unknown")

    @property
    def config(self):
        """The resolved config overlay (see apply.overlay_plan)."""
        return self.payload.get("config", {})

    @property
    def probed(self):
        return bool(self.payload.get("probed"))

    def cache_path(self, cache_dir=None):
        shape_key = self.payload.get("shape_key", "unknown")
        return os.path.join(
            plan_cache_dir(cache_dir),
            f"plan-{_slug(self.device_kind)}-{_slug(shape_key)}.json")

    def save(self, path=None, cache_dir=None):
        """Atomic write (tmp + rename): a crashed `ds_plan` must not
        leave a torn JSON where the engine will read it."""
        path = path or self.cache_path(cache_dir)
        os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
        tmp = f"{path}.tmp.{os.getpid()}"
        with open(tmp, "w") as f:
            json.dump(self.payload, f, indent=2, sort_keys=True,
                      default=str)
            f.write("\n")
        os.replace(tmp, path)
        return path

    def to_json(self):
        return json.dumps(self.payload, indent=2, sort_keys=True,
                          default=str)


def load_plan(path):
    """Load + re-fingerprint a plan file; a payload whose recorded
    fingerprint disagrees with its content raises (a hand-edited plan
    must be re-emitted through `ds_plan`, not trusted silently)."""
    with open(path) as f:
        payload = json.load(f)
    recorded = payload.get("fingerprint")
    plan = Plan(payload)
    if recorded and recorded != plan.fingerprint:
        raise ValueError(
            f"plan file {path} fingerprint mismatch: recorded "
            f"{recorded!r}, content hashes to {plan.fingerprint!r} — "
            f"re-emit it with ds_plan instead of hand-editing")
    return plan


def cached_plan(device_kind, shape_key, cache_dir=None):
    """The persisted plan for (device kind, model shape), or None —
    the warm-cache path: a hit performs zero probes."""
    path = os.path.join(
        plan_cache_dir(cache_dir),
        f"plan-{_slug(device_kind)}-{_slug(shape_key)}.json")
    if not os.path.exists(path):
        return None
    try:
        return load_plan(path)
    except Exception:  # noqa: BLE001 - torn/stale cache = replan
        return None


def latest_plan(cache_dir=None):
    """Newest persisted plan in the cache (what `ds_report --json`
    surfaces), or None."""
    root = plan_cache_dir(cache_dir)
    try:
        files = [os.path.join(root, f) for f in os.listdir(root)
                 if f.startswith("plan-") and f.endswith(".json")]
    except OSError:
        return None
    for path in sorted(files, key=os.path.getmtime, reverse=True):
        try:
            return load_plan(path)
        except Exception:  # noqa: BLE001 - skip torn files
            continue
    return None


def latest_plan_fingerprint(cache_dir=None):
    plan = latest_plan(cache_dir)
    return plan.fingerprint if plan is not None else None
