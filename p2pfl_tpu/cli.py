"""Command-line interface.

Reference: Typer app with ``experiment list`` / ``experiment run``
(``p2pfl/cli.py:65-203``), a Rich logo banner and Rich tables. argparse
here (typer/rich aren't in this image); same surface — examples are
discovered from ``p2pfl_tpu/examples/`` and run in-process with their own
argv — with a dependency-free equivalent of the Rich UX: an ANSI banner
and box-drawing tables on a UTF-8 interactive terminal; pipes and
ASCII-only stdouts keep the plain machine-parseable two-column listing.
"""

from __future__ import annotations

import argparse
import importlib
import pkgutil
import sys

_BANNER = r"""
  ___ ___ ___ ___ _      _____ ___ _   _
 | _ \_  ) _ \ __| |    |_   _| _ \ | | |
 |  _// /|  _/ _|| |__    | | |  _/ |_| |
 |_| /___|_| |_| |____|   |_| |_|  \___/
"""


def _fancy() -> bool:
    """Decorate only for a UTF-8-capable interactive terminal — a pipe or
    an ASCII-only stdout keeps the plain machine-parseable two-column form
    (the pre-round-5 output)."""
    if not sys.stdout.isatty():
        return False
    try:
        "┌".encode(getattr(sys.stdout, "encoding", "") or "ascii")
    except (UnicodeEncodeError, LookupError):
        return False
    return True


def _color(s: str, code: str) -> str:
    return f"\033[{code}m{s}\033[0m"


def _banner() -> str:
    return _color(_BANNER, "34") + _color(
        "  peer-to-peer federated learning, TPU-native\n", "2"
    )


def _table(headers: list[str], rows: list[list[str]]) -> str:
    """Box-drawing table (no ANSI — pure glyphs) — the in-image stand-in
    for Rich's Table (reference ``cli.py:112-125``)."""
    widths = [
        max(len(h), *(len(r[i]) for r in rows)) if rows else len(h)
        for i, h in enumerate(headers)
    ]

    def line(left: str, mid: str, right: str, fill: str = "─") -> str:
        return left + mid.join(fill * (w + 2) for w in widths) + right

    def row(cells: list[str]) -> str:
        return "│" + "│".join(f" {c:<{w}} " for w, c in zip(widths, cells)) + "│"

    parts = [line("┌", "┬", "┐"), row(headers), line("├", "┼", "┤")]
    parts += [row(r) for r in rows]
    parts.append(line("└", "┴", "┘"))
    return "\n".join(parts)


def _discover() -> dict[str, str]:
    """Example name → first docstring line."""
    import p2pfl_tpu.examples as ex

    out = {}
    for info in pkgutil.iter_modules(ex.__path__):
        mod = importlib.import_module(f"p2pfl_tpu.examples.{info.name}")
        doc = (mod.__doc__ or "").strip().splitlines()
        out[info.name] = doc[0] if doc else ""
    return out


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(prog="p2pfl_tpu", description="TPU-native federated learning")
    sub = parser.add_subparsers(dest="command")

    exp = sub.add_parser("experiment", help="list or run bundled experiments")
    exp_sub = exp.add_subparsers(dest="action")
    exp_sub.add_parser("list", help="list available experiments")
    run = exp_sub.add_parser("run", help="run an experiment by name")
    run.add_argument("name")
    run.add_argument("extra", nargs=argparse.REMAINDER, help="arguments passed to the experiment")

    sub.add_parser("bench", help="run the headline benchmark")
    # remote-management verbs are stubs in the reference too (cli.py:71-95)
    for stub in ("login", "remote", "launch"):
        sub.add_parser(stub, help="(coming soon)")

    args = parser.parse_args(argv)
    if args.command in ("login", "remote", "launch"):
        print(f"{args.command}: coming soon (stub — reference parity, cli.py:71-95)")
        return 0
    if args.command == "experiment":
        if args.action == "list":
            entries = sorted(_discover().items())
            if _fancy():
                print(_banner())
                print(_table(["experiment", "description"], [[n, d] for n, d in entries]))
            else:
                for name, doc in entries:
                    print(f"{name:20s} {doc}")
            return 0
        if args.action == "run":
            examples = _discover()
            if args.name not in examples:
                print(f"unknown experiment {args.name!r}; try: {', '.join(sorted(examples))}")
                return 1
            from p2pfl_tpu.compile_cache import configure_compile_cache

            configure_compile_cache()
            mod = importlib.import_module(f"p2pfl_tpu.examples.{args.name}")
            mod.main(args.extra)
            return 0
        exp.print_help()
        return 1
    if args.command == "bench":
        import runpy
        from pathlib import Path

        # bench.py sits beside the package in a checkout (it is not
        # installed with it); resolve it from there, not from the cwd
        bench = Path(__file__).resolve().parent.parent / "bench.py"
        if not bench.is_file():
            print(f"bench: {bench} not found (run from a source checkout)")
            return 1
        runpy.run_path(str(bench), run_name="__main__")
        return 0
    parser.print_help()
    return 1


if __name__ == "__main__":
    sys.exit(main())
