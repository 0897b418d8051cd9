"""Command registry + lifecycle — ADAMMain / ADAMSparkCommand analog.

``python -m adam_tpu.cli.main <command> [args]`` (or the ``adam-tpu``
console script). The registry mirrors ``ADAMMain.scala:30-72`` — three
groups, same command names. The lifecycle mirrors
``ADAMCommand.scala:43-91``: parse args, optionally enable the metrics
registry, run, print the timing report on ``-print_metrics``.
"""

from __future__ import annotations

import argparse
import contextlib
import sys

from adam_tpu.utils import instrumentation as ins
from adam_tpu.utils import telemetry as tele


class Command:
    """One CLI subcommand: subclasses set name/description and implement
    configure/run (ADAMCommandCompanion + ADAMCommand)."""

    name: str = ""
    description: str = ""

    @classmethod
    def configure(cls, parser: argparse.ArgumentParser) -> None:
        pass

    @classmethod
    def run(cls, args: argparse.Namespace) -> int | None:
        raise NotImplementedError


def add_common_args(parser: argparse.ArgumentParser) -> None:
    """Args4jBase + ParquetArgs flags shared by every command
    (Args4j.scala:23-28, ParquetArgs.scala:24-35)."""
    parser.add_argument(
        "-print_metrics", action="store_true",
        help="print metrics to the log on completion (timer table plus "
        "the telemetry counters/gauges recorded under it)",
    )
    parser.add_argument(
        "--metrics-json", dest="metrics_json", default=None, metavar="PATH",
        help="write the telemetry snapshot (spans, counters, gauges, and "
        "the timer table as machine-readable JSON) to PATH on completion",
    )
    parser.add_argument(
        "--trace-out", dest="trace_out", default=None, metavar="PATH",
        help="write the flight recorder as a Chrome-trace JSON file "
        "loadable in chrome://tracing or Perfetto (per-thread tracks "
        "show the streamed tokenize/dispatch/encode/write overlap)",
    )
    parser.add_argument(
        "--progress", dest="progress", nargs="?", const="stderr",
        default=None, metavar="PATH",
        help="emit a live NDJSON progress heartbeat every few seconds "
        "(windows done/total, reads/s, bytes written, per-device "
        "in-flight depth, retry/fault/evict counters, ETA) to stderr, "
        "or to PATH when given; also honored from ADAM_TPU_PROGRESS, "
        "period from ADAM_TPU_PROGRESS_INTERVAL_S (streamed transform "
        "only; schema in docs/OBSERVABILITY.md)",
    )
    parser.add_argument(
        "--devices", dest="devices", type=int, default=None, metavar="N",
        help="fan device work out over N attached chips (the streamed "
        "pipeline round-robins windows across them; default: all "
        "attached, or ADAM_TPU_DEVICES; N=1 forces the single-device "
        "path; requests beyond the attached count are capped)",
    )
    parser.add_argument(
        "--partitioner", dest="partitioner", default=None,
        choices=["pool", "mesh"],
        help="how the streamed pipeline places device work across the "
        "chips: 'pool' (default) round-robins whole windows with "
        "host-side histogram merges; 'mesh' shards every window over a "
        "batch Mesh, psums the BQSR observe histograms on-device (one "
        "merged table crosses at barrier 2 instead of one per window) "
        "and keeps the solved table device-resident through pass C — "
        "bit-identical output, degrades to 'pool' on device failure; "
        "also honored from ADAM_TPU_PARTITIONER",
    )
    parser.add_argument(
        "--fault-spec", dest="fault_spec", default=None, metavar="SPEC",
        help="arm deterministic fault injection at named pipeline "
        "points (testing/CI only; e.g. 'device.dispatch=transient,"
        "every=3' — grammar in docs/ROBUSTNESS.md; also honored from "
        "ADAM_TPU_FAULTS)",
    )
    parser.add_argument(
        "--xprof-dir", dest="xprof_dir", default=None, metavar="DIR",
        help="wrap the command in a jax profiler trace written to DIR "
        "(xprof/TensorBoard view of the device work; reentrant-safe "
        "no-op if a trace is already active)",
    )
    parser.add_argument(
        "-log_level", default="warning",
        choices=["debug", "info", "warning", "error"],
        help="adam_tpu logging verbosity (debug shows per-target "
        "realignment LOD decisions and BQSR visit accounting)",
    )
    parser.add_argument(
        "-stringency", default="lenient",
        choices=["strict", "lenient", "silent"],
        help="validation stringency for malformed-input handling "
        "(FASTQ pairing/export paths)",
    )
    parser.add_argument(
        "-parquet_compression_codec", default="zstd",
        choices=["uncompressed", "snappy", "gzip", "zstd"],
        help="parquet compression codec",
    )
    parser.add_argument(
        "-parquet_block_size", type=int, default=128 * 1024 * 1024,
        help="parquet block size (accepted for parity; row-group sizing)",
    )
    parser.add_argument(
        "-parquet_page_size", type=int, default=1024 * 1024,
        help="parquet page size (accepted for parity)",
    )
    parser.add_argument(
        "-parquet_disable_dictionary", action="store_true",
        help="disable parquet dictionary encoding (accepted for parity)",
    )


def command_groups():
    from adam_tpu.cli import actions, conversions, devtools, printers

    return [
        ("ADAM ACTIONS", actions.COMMANDS),
        ("CONVERSION OPERATIONS", conversions.COMMANDS),
        ("PRINT", printers.COMMANDS),
        ("DEVELOPMENT", devtools.COMMANDS),
    ]


def _usage() -> str:
    out = ["", "Usage: adam-tpu COMMAND", ""]
    for group, commands in command_groups():
        out.append(group)
        for cmd in commands:
            out.append(f"{cmd.name:>20} : {cmd.description}")
        out.append("")
    return "\n".join(out)


def main(argv=None) -> int:
    argv = list(sys.argv[1:] if argv is None else argv)
    registry = {c.name: c for _, cmds in command_groups() for c in cmds}
    if not argv or argv[0] in ("-h", "--help"):
        print(_usage())
        return 0
    name, rest = argv[0], argv[1:]
    if name not in registry:
        print(f"unknown command: {name}", file=sys.stderr)
        print(_usage(), file=sys.stderr)
        return 1
    cmd = registry[name]
    parser = argparse.ArgumentParser(
        prog=f"adam-tpu {name}", description=cmd.description,
        # reference flags are single-dash long options (args4j); argparse
        # prefix matching would make flag typos silently match — disable
        allow_abbrev=False,
    )
    add_common_args(parser)
    cmd.configure(parser)
    args = parser.parse_args(rest)
    import logging

    logging.basicConfig(
        level=getattr(logging, args.log_level.upper()),
        format="%(asctime)s %(name)s %(levelname)s: %(message)s",
    )
    # any observability sink switches recording on: the timer table, the
    # JSON snapshot, the Chrome trace, the analyzer report and the
    # profiler trace (whose host plane carries the recorded spans) all
    # read the same run (--progress self-manages via the heartbeat)
    want_metrics = bool(
        args.print_metrics or args.metrics_json or args.trace_out
        or getattr(args, "report", None) or args.xprof_dir
    )
    ins.TIMERS.recording = want_metrics
    tele.TRACE.recording = want_metrics
    if args.fault_spec:
        from adam_tpu.utils import faults

        try:
            faults.install(args.fault_spec)
        except ValueError as e:
            print(f"--fault-spec: {e}", file=sys.stderr)
            return 2
    xprof = (
        ins.device_trace(args.xprof_dir) if args.xprof_dir
        else contextlib.nullcontext()
    )
    try:
        with xprof:
            rc = cmd.run(args)
    except BrokenPipeError:  # e.g. `adam-tpu print ... | head`
        try:
            sys.stdout.close()
        except Exception:
            pass
        return 0
    finally:
        if args.print_metrics:
            try:
                print(ins.TIMERS.report())
                print(tele.TRACE.report())
            except BrokenPipeError:
                pass
        for path, dump in (
            (args.metrics_json, tele.TRACE.dump_json),
            (args.trace_out, tele.TRACE.dump_chrome_trace),
        ):
            if path:
                try:
                    dump(path)
                except OSError as e:
                    print(f"telemetry export to {path} failed: {e}",
                          file=sys.stderr)
    return int(rc or 0)


if __name__ == "__main__":
    sys.exit(main())
