"""oncilla-tpu: a TPU-native disaggregated-memory runtime.

Capabilities of jyoung3131/oncilla (OncillaMem) rebuilt TPU-first: opaque
allocation handles over local HBM / local host DRAM / remote-chip HBM /
remote-host DRAM, one-sided put/get, a daemon control plane with rank-0
placement, ICI (Pallas remote DMA / ppermute) and DCN data planes.

Public API mirrors inc/oncillamem.h:69-89 of the reference.
"""

from oncilla_tpu.core.arena import ArenaAllocator, Extent
from oncilla_tpu.core.context import (
    Ocm,
    ocm_alloc,
    ocm_alloc_kind,
    ocm_copy,
    ocm_copy_in,
    ocm_copy_onesided,
    ocm_copy_out,
    ocm_free,
    ocm_init,
    ocm_is_remote,
    ocm_localbuf,
    ocm_remote_sz,
    ocm_tini,
)
from oncilla_tpu.core.errors import (
    OcmAdmissionDenied,
    OcmBoundsError,
    OcmBreakerOpen,
    OcmBusy,
    OcmConnectError,
    OcmDeadlineExceeded,
    OcmError,
    OcmInvalidHandle,
    OcmMoved,
    OcmNotPrimary,
    OcmOutOfMemory,
    OcmPlacementError,
    OcmProtocolError,
    OcmQuotaExceeded,
    OcmRemoteError,
    OcmReplicaUnavailable,
)
from oncilla_tpu.core.handle import OcmAlloc
from oncilla_tpu.core.kinds import Fabric, OcmKind
from oncilla_tpu.utils.config import OcmConfig

__version__ = "0.1.0"

__all__ = [
    "ArenaAllocator",
    "Extent",
    "Fabric",
    "Ocm",
    "OcmAdmissionDenied",
    "OcmAlloc",
    "OcmBoundsError",
    "OcmBreakerOpen",
    "OcmBusy",
    "OcmConfig",
    "OcmConnectError",
    "OcmDeadlineExceeded",
    "OcmError",
    "OcmInvalidHandle",
    "OcmKind",
    "OcmMoved",
    "OcmNotPrimary",
    "OcmOutOfMemory",
    "OcmPlacementError",
    "OcmProtocolError",
    "OcmQuotaExceeded",
    "OcmRemoteError",
    "OcmReplicaUnavailable",
    "ocm_alloc",
    "ocm_alloc_kind",
    "ocm_copy",
    "ocm_copy_in",
    "ocm_copy_onesided",
    "ocm_copy_out",
    "ocm_free",
    "ocm_init",
    "ocm_is_remote",
    "ocm_localbuf",
    "ocm_remote_sz",
    "ocm_tini",
]
