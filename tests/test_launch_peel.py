"""``launch/peel.py`` argument checks: every flag combination the
launcher refuses raises its own ``LaunchError`` before any graph is
built, and an absent ``--engine`` resolves to each graph source's
default.  No test here compiles or peels."""
import pytest

from repro.launch.peel import LaunchError, _validate, build_parser


def _args(*argv):
    return build_parser().parse_args(list(argv))


# (argv, device count, a fragment of the error the branch raises)
REFUSED = {
    "edges-with-dataset": (
        ["--edges", "g.tsv", "--dataset", "fr"], 1, "exclusive"),
    "edges-multi-device": (
        ["--edges", "g.tsv"], 4, "single-device"),
    "tip-beindex": (
        ["--kind", "tip", "--engine", "beindex"], 1, "no BE-Index tip"),
    "use-pallas-off-csr": (
        ["--engine", "dense", "--use-pallas"], 1, "--use-pallas routes"),
    "vmapped-off-csr": (
        ["--engine", "beindex", "--fd-driver", "vmapped"], 1,
        "vmapped is the csr"),
    "aligned-dense": (
        ["--engine", "dense", "--aligned"], 4, "no sharded index"),
    "multi-dense-wing": (
        ["--kind", "wing", "--engine", "dense"], 4,
        "no distributed dense wing"),
    "multi-vmapped-wing": (
        ["--kind", "wing", "--engine", "csr", "--fd-driver", "vmapped"],
        4, "distributed wing FD"),
    "multi-host-driver": (
        ["--kind", "tip", "--engine", "csr", "--fd-driver", "host"], 4,
        "single-device A/B"),
    "multi-use-pallas": (
        ["--kind", "tip", "--engine", "csr", "--use-pallas"], 4,
        "wired for the single-device"),
    "aligned-one-device": (
        ["--engine", "csr", "--aligned"], 1, "multi-device mesh"),
}


@pytest.mark.parametrize("case", list(REFUSED))
def test_validate_refuses(case):
    argv, n_dev, fragment = REFUSED[case]
    with pytest.raises(LaunchError) as e:
        _validate(_args(*argv), n_dev)
    assert fragment in str(e.value), str(e.value)


@pytest.mark.parametrize("argv,engine", [
    (["--edges", "g.tsv", "--kind", "wing"], "csr"),
    (["--kind", "wing"], "beindex"),
    (["--kind", "tip"], "csr"),
], ids=["edges", "wing", "tip"])
def test_validate_resolves_engine_default(argv, engine):
    args = _args(*argv)
    assert args.engine is None
    _validate(args, 1)
    assert args.engine == engine


def test_fused_fd_flag_is_gone():
    with pytest.raises(SystemExit) as e:
        _args("--kind", "wing", "--engine", "csr", "--fused-fd")
    assert e.value.code == 2
