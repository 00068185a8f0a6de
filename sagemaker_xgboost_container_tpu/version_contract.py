"""Single source of truth for the framework's dependency-version contract.

The reference pins its dependency set in the image and asserts it from
inside a running container (reference test/integration/local/
test_versions.py + test/resources/versions/train.py). Here the contract
lives in one importable module consumed by three enforcement points:

* ``setup.py`` turns SUPPORTED into ``install_requires`` specifiers, so pip
  refuses to install the package against an unsupported stack;
* the image build gate (docker/Dockerfile.tpu) calls :func:`assert_supported`
  so an image never ships with a drifted dependency;
* ``tests/test_versions.py`` asserts the live environment satisfies the
  contract (the in-repo analog of the reference's in-image version test).

This module must stay importable WITHOUT the package's dependencies
installed (setup.py loads it before they exist) — stdlib imports only at
module level.
"""

# floors = the one installation the framework is built, tested and run on
# the chip against (libtpu 0.0.34 rides the TPU image's jax[tpu] extra and
# is absent from CPU images, so it is not an install requirement); no upper
# bounds (jax moves fast and upper-pinning a container base image causes
# more breakage than it prevents — raise deliberately, with tests)
SUPPORTED = {
    "jax": ">=0.9.0",
    "jaxlib": ">=0.9.0",
    "numpy": ">=2.0.2",
    "scipy": ">=1.17.0",
    "pandas": ">=3.0.3",
    "pyarrow": ">=25.0.0",
    "scikit-learn": ">=1.9.0",
    "protobuf": ">=6.33.5",
    # violations() itself needs it, and python:…-slim images don't ship it
    # (pip only vendors a private copy)
    "packaging": ">=26.0",
}


def install_requires():
    """setup.py install_requires list derived from the contract."""
    return [name + spec for name, spec in sorted(SUPPORTED.items())]


def violations():
    """[(package, installed_version_or_None, required_spec), ...] for every
    contract entry the live environment fails."""
    import importlib.metadata as md

    from packaging.specifiers import SpecifierSet
    from packaging.version import Version

    bad = []
    for name, spec in sorted(SUPPORTED.items()):
        try:
            installed = md.version(name)
        except md.PackageNotFoundError:
            bad.append((name, None, spec))
            continue
        if Version(installed) not in SpecifierSet(spec):
            bad.append((name, installed, spec))
    return bad


def assert_supported():
    """Raise RuntimeError listing every contract violation (image gate)."""
    bad = violations()
    if bad:
        raise RuntimeError(
            "dependency contract violated: "
            + "; ".join(
                "{} {} (need {})".format(n, v or "MISSING", s) for n, v, s in bad
            )
        )


if __name__ == "__main__":
    assert_supported()
    print("dependency contract OK")
