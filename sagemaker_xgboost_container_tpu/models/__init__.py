from .. import import_began as _import_began, note_import as _note_import

_IMPORT_BEGAN = _import_began()  # the import that brings `train` in: startup.package_import

from .booster import TrainConfig, train  # noqa: E402,F401
from .forest import Forest, Tree  # noqa: E402,F401
from .objectives import create_objective  # noqa: E402,F401

# familiar alias for script-mode users porting xgboost code
Booster = Forest

_note_import(_IMPORT_BEGAN)
