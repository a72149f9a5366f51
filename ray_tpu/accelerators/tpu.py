"""TPU accelerator manager: chip detection + slice topology discovery.

Counterpart of the reference's TPUAcceleratorManager (reference:
python/ray/_private/accelerators/tpu.py:71-397):

- chip detection via ``/dev/accel*`` and ``/dev/vfio`` device files (tpu.py:98-117)
- pod type / worker id / pod name from TPU-VM env or GCE metadata (tpu.py:48-68,
  198-271); here env vars take precedence, ``TPU_SKIP_MDS_QUERY`` (libtpu's own
  switch) turns the metadata server off, and it is addressed by its link-local
  IP so a machine with no network pays at most the connect timeout, never a
  DNS stall
- ``TPU_VISIBLE_CHIPS`` visibility for workers (tpu.py:155-195)
- gang-scheduling resources: ``TPU-{pod_type}-head`` advertised only by worker 0
  of a slice, plus a per-slice name resource, so a placement group of
  [{TPU-v5e-16-head: 1}, {tpu-slice-name: 1} x (hosts-1)] lands one actor per
  host of one slice (tpu.py:334-397)
- valid chip counts per host: {1, 2, 4, 8} (tpu.py:14,141-152)

Test hook: ``RAY_TPU_FAKE_TPU_CHIPS`` / ``RAY_TPU_FAKE_TPU_POD_TYPE`` /
``RAY_TPU_FAKE_TPU_WORKER_ID`` fake the hardware the way the reference mocks
``/dev/accel*`` in python/ray/tests/accelerators/test_tpu.py.
"""

from __future__ import annotations

import glob
import logging
import os
from typing import Dict, List, Optional

from ray_tpu.accelerators.accelerator import AcceleratorManager

logger = logging.getLogger(__name__)

VALID_CHIPS_PER_HOST = (1, 2, 4, 8)
# metadata.google.internal by its fixed link-local address: resolving the name
# is the one step urlopen's timeout does not bound.
GCE_METADATA_URL = "http://169.254.169.254/computeMetadata/v1/instance"


def _metadata(path: str) -> Optional[str]:
    """Poll GCE instance metadata; None when unreachable (non-GCE / sandbox)
    or switched off with ``TPU_SKIP_MDS_QUERY``."""
    if os.environ.get("TPU_SKIP_MDS_QUERY", "").lower() in ("1", "true"):
        return None
    try:
        import urllib.request

        req = urllib.request.Request(
            f"{GCE_METADATA_URL}/{path}", headers={"Metadata-Flavor": "Google"}
        )
        with urllib.request.urlopen(req, timeout=0.5) as resp:
            return resp.read().decode()
    except Exception:
        return None


class TPUAcceleratorManager(AcceleratorManager):
    def get_resource_name(self) -> str:
        return "TPU"

    # -- detection ------------------------------------------------------------
    def get_current_node_num_accelerators(self) -> int:
        fake = os.environ.get("RAY_TPU_FAKE_TPU_CHIPS")
        if fake:
            return int(fake)
        visible = os.environ.get("TPU_VISIBLE_CHIPS")
        if visible:
            return len([c for c in visible.split(",") if c != ""])
        accel = glob.glob("/dev/accel*")
        if accel:
            return len(accel)
        try:
            vfio = os.listdir("/dev/vfio")
            return len([f for f in vfio if f != "vfio"])
        except FileNotFoundError:
            return 0

    def get_current_pod_type(self) -> Optional[str]:
        """Slice type, e.g. 'v5e-16' (reference tpu.py accelerator-type metadata)."""
        for var in ("RAY_TPU_FAKE_TPU_POD_TYPE", "TPU_ACCELERATOR_TYPE", "TPU_TYPE"):
            v = os.environ.get(var)
            if v:
                return v
        if self.get_current_node_num_accelerators() == 0:
            return None
        return _metadata("attributes/accelerator-type")

    def get_current_pod_worker_id(self) -> Optional[int]:
        for var in ("RAY_TPU_FAKE_TPU_WORKER_ID", "TPU_WORKER_ID", "CLOUD_TPU_TASK_ID"):
            v = os.environ.get(var)
            if v is not None and v != "":
                return int(v)
        if self.get_current_node_num_accelerators() == 0:
            return None
        v = _metadata("attributes/agent-worker-number")
        return int(v) if v is not None else None

    def get_current_pod_name(self) -> Optional[str]:
        for var in ("RAY_TPU_FAKE_TPU_POD_NAME", "TPU_NAME", "TPU_POD_NAME"):
            v = os.environ.get(var)
            if v:
                return v
        if self.get_current_node_num_accelerators() == 0:
            return None
        return _metadata("attributes/instance-id")

    def get_num_workers_in_pod(self) -> int:
        pod_type = self.get_current_pod_type()
        if not pod_type:
            return 0
        try:
            # 'v5e-16' -> 16 chips total; hosts = chips / chips_per_host
            total_chips = int(pod_type.rsplit("-", 1)[1])
        except (ValueError, IndexError):
            return 0
        per_host = self.get_current_node_num_accelerators() or 4
        return max(1, total_chips // max(per_host, 1))

    # -- resources ------------------------------------------------------------
    def get_current_node_additional_resources(self) -> Dict[str, float]:
        """The SPMD gang-scheduling resources (reference tpu.py:334-397)."""
        res: Dict[str, float] = {}
        pod_type = self.get_current_pod_type()
        worker_id = self.get_current_pod_worker_id()
        pod_name = self.get_current_pod_name()
        if pod_type and worker_id == 0:
            res[f"TPU-{pod_type}-head"] = 1.0
        if pod_name:
            res[pod_name] = 1.0
        return res

    def get_visible_accelerator_ids_env_var(self) -> Optional[str]:
        return "TPU_VISIBLE_CHIPS"

    def validate_resource_request_quantity(self, quantity: float) -> Optional[str]:
        if quantity != int(quantity) or (int(quantity) not in VALID_CHIPS_PER_HOST
                                         and quantity != 0):
            return (
                f"TPU request of {quantity} is invalid: a task can use "
                f"{VALID_CHIPS_PER_HOST} whole chips on one host; whole-slice "
                f"jobs should request TPU-{{pod_type}}-head + per-host gangs instead."
            )
        return None
