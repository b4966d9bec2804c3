"""Protocol zoo: commit-protocol presets, mode constants, and the registry.

Public surface:
  - `ProtocolConfig` + the STAGGER_*/PREPARE_* mode constants (`base`)
  - `PRESETS` (frozen name -> ProtocolConfig view) and `register_preset`
    (`registry`)
  - the built-in preset instances (`presets`) — importing this package
    registers them

`repro.core.protocol` (singular) remains a legacy re-export shim of this
package, so existing imports keep working unchanged.
"""

from bench.ref.protocols.base import (
    PREPARE_COORD,
    PREPARE_DECENTRAL,
    PREPARE_NONE,
    STAGGER_NET,
    STAGGER_NET_LEL,
    STAGGER_NONE,
    ProtocolConfig,
)
from bench.ref.protocols.presets import (
    CHILLER,
    FASTC,
    GEOTP,
    GEOTP_O1,
    GEOTP_O12,
    OPTA,
    QURO,
    SCALARDB,
    SSP,
    SSP_LOCAL,
    TIGA,
    YUGA,
)
from bench.ref.protocols.registry import PRESETS, register_preset

__all__ = [
    "PREPARE_COORD",
    "PREPARE_DECENTRAL",
    "PREPARE_NONE",
    "STAGGER_NET",
    "STAGGER_NET_LEL",
    "STAGGER_NONE",
    "ProtocolConfig",
    "PRESETS",
    "register_preset",
    "SSP",
    "SSP_LOCAL",
    "SCALARDB",
    "QURO",
    "CHILLER",
    "YUGA",
    "GEOTP_O1",
    "GEOTP_O12",
    "GEOTP",
    "FASTC",
    "TIGA",
    "OPTA",
]
