from .. import import_began as _import_began, note_import as _note_import

_IMPORT_BEGAN = _import_began()  # a job's imports: the span startup.package_import

from .algorithm_train import sagemaker_train, train_job  # noqa: E402,F401

_note_import(_IMPORT_BEGAN)
