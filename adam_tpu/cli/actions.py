"""ADAM ACTIONS command group (ADAMMain.scala:32-48).

depth, count_kmers, count_contig_kmers, transform, adam2fastq, plugin,
flatten — each docstring cites the reference command it mirrors.
"""

from __future__ import annotations

import sys

import numpy as np

from adam_tpu.cli.main import Command
from adam_tpu.utils import instrumentation as ins


def _write_kmer_counts(counts: dict, output: str, print_histogram: bool):
    """Shared '(kmer, count)' text output + optional count histogram
    (the saveAsTextFile tail of CountReadKmers/CountContigKmers).
    k-mer counts stay ints; q-mer weights stay floats."""
    if print_histogram:
        hist: dict[int, int] = {}
        for v in counts.values():
            hist[int(v)] = hist.get(int(v), 0) + 1
        for k in sorted(hist):
            print((k, hist[k]))
    with open(output, "w") as fh:
        for kmer, v in counts.items():
            fh.write(f"{kmer}, {v}\n")


class CalculateDepth(Command):
    """Read depth at each variant of a VCF via broadcast region join
    (adam-cli CalculateDepth.scala:41-120)."""

    name = "depth"
    description = "Calculate the depth from a given ADAM file, at each variant in a VCF"

    @classmethod
    def configure(cls, p):
        p.add_argument("adam", metavar="ADAM",
                       help="The read file to use to calculate depths")
        p.add_argument("vcf", metavar="VCF",
                       help="The VCF containing the sites at which to calculate depths")
        p.add_argument("-cartesian", action="store_true",
                       help="use a cartesian join, then filter")
        p.add_argument("-stream", action="store_true",
                       help="out-of-core: stream the reads through a "
                            "genome-bin shard spill and join one bin at "
                            "a time (bounded memory on WGS-scale input)")
        p.add_argument("-bin_size", type=int, default=1_000_000,
                       help="genome bin width for -stream (default 1Mbp)")

    @classmethod
    def run(cls, args):
        from adam_tpu.api.datasets import AlignmentDataset, GenotypeDataset
        from adam_tpu.io import context
        from adam_tpu.pipelines.region_join import (
            IntervalArrays,
            broadcast_region_join,
        )

        proj = None
        if str(args.adam).endswith((".adam", ".parquet")):
            # depth only joins on coordinates: push the projection down
            # so payload columns (sequence/qual/attrs) are never read
            proj = ["contig", "start", "end", "flags"]
        if args.stream:
            # out-of-core path: header first for
            # the dictionary, then windows through the bin spill
            header = context.load_header(args.adam)
            gt = GenotypeDataset.load(
                args.vcf, contig_names=header.seq_dict.names
            )
            sites = IntervalArrays.of(
                gt.variants.contig_idx,
                gt.variants.start,
                gt.variants.start + 1,
            )
            from adam_tpu.parallel.sharded_join import streamed_depth

            depth = streamed_depth(
                context.iter_alignment_batches(args.adam, projection=proj),
                sites, header.seq_dict, bin_size=args.bin_size,
            )
        else:
            kw = {"projection": proj} if proj else {}
            ds = AlignmentDataset.load(args.adam, **kw)
            b = ds.batch.to_numpy()
            mapped = np.flatnonzero(
                np.asarray(b.is_mapped) & np.asarray(b.valid)
            )
            reads = IntervalArrays.of(
                b.contig_idx[mapped], b.start[mapped], b.end[mapped]
            )
            gt = GenotypeDataset.load(
                args.vcf, contig_names=ds.seq_dict.names
            )
            sites = IntervalArrays.of(
                gt.variants.contig_idx,
                gt.variants.start,
                gt.variants.start + 1,  # variant *position*, as the
                # reference keys it
            )
            si, _ri = broadcast_region_join(sites, reads)
            depth = np.bincount(si, minlength=len(sites))
        names = gt.variants.sidecar.names
        # gt.contig_names is the extended space: it includes VCF-only
        # contigs appended past the read dictionary
        contig_names = gt.contig_names
        print("location\tname\tdepth")
        order = np.lexsort((gt.variants.start, gt.variants.contig_idx))
        for i in order:
            loc = "%s:%d" % (
                contig_names[gt.variants.contig_idx[i]],
                int(gt.variants.start[i]),
            )
            print("%20s\t%15s\t% 5d" % (loc, names[i] or ".", int(depth[i])))
        return 0


class CountReadKmers(Command):
    """k-mers/q-mers from a read dataset (CountReadKmers.scala:30-100)."""

    name = "count_kmers"
    description = "Counts the k-mers/q-mers from a read dataset."

    @classmethod
    def configure(cls, p):
        p.add_argument("input", metavar="INPUT")
        p.add_argument("output", metavar="OUTPUT",
                       help="Location for storing k-mer counts")
        p.add_argument("kmer_length", metavar="KMER_LENGTH", type=int)
        p.add_argument("-countQmers", action="store_true",
                       help="counts q-mers instead of k-mers")
        p.add_argument("-printHistogram", action="store_true",
                       help="prints a histogram of counts")
        p.add_argument("-repartition", type=int, default=-1,
                       help="accepted for parity; batches need no repartition")

    @classmethod
    def run(cls, args):
        from adam_tpu.io import context

        with ins.TIMERS.time(ins.LOAD_ALIGNMENTS):
            kw = {}
            if str(args.input).endswith((".adam", ".parquet")):
                kw["projection"] = ["sequence", "qual"]
            ds = context.load_alignments(args.input, **kw)
        with ins.TIMERS.time(ins.COUNT_KMERS):
            if args.countQmers:
                counts = ds.count_qmers(args.kmer_length)
            else:
                counts = ds.count_kmers(args.kmer_length)
        _write_kmer_counts(counts, args.output, args.printHistogram)
        return 0


class CountContigKmers(Command):
    """k-mers over reference contigs (CountContigKmers.scala:29-90)."""

    name = "count_contig_kmers"
    description = "Counts the k-mers/q-mers from a contig dataset."

    @classmethod
    def configure(cls, p):
        p.add_argument("input", metavar="INPUT",
                       help="The ADAM or FASTA file to count kmers from")
        p.add_argument("output", metavar="OUTPUT")
        p.add_argument("kmer_length", metavar="KMER_LENGTH", type=int)
        p.add_argument("-printHistogram", action="store_true")

    @classmethod
    def run(cls, args):
        from adam_tpu.formats.fragments import count_contig_kmers
        from adam_tpu.io import context, parquet

        if str(args.input).endswith((".fa", ".fasta", ".fa.gz", ".fasta.gz")):
            fragments, _sd, _desc = context.load_fasta(args.input)
        else:
            fragments, _sd, _desc = parquet.load_fragments(args.input)
        with ins.TIMERS.time(ins.COUNT_KMERS):
            counts = count_contig_kmers(fragments, args.kmer_length)
        _write_kmer_counts(counts, args.output, args.printHistogram)
        return 0


class Transform(Command):
    """THE pipeline — flag-composed read preprocessing
    (Transform.scala:101-179; same stage order)."""

    name = "transform"
    description = ("Convert SAM/BAM to ADAM format and optionally perform "
                   "read pre-processing transformations")

    @classmethod
    def configure(cls, p):
        p.add_argument("input", metavar="INPUT",
                       help="The ADAM, BAM or SAM file to apply the transforms to")
        p.add_argument("output", metavar="OUTPUT",
                       help="Location to write the transformed data")
        p.add_argument("-sort_reads", action="store_true")
        p.add_argument("-mark_duplicate_reads", action="store_true")
        p.add_argument("-recalibrate_base_qualities", action="store_true")
        p.add_argument("-dump_observations", default=None,
                       help="local path to dump BQSR observations to (CSV)")
        p.add_argument("-known_snps", default=None,
                       help="sites-only VCF giving location of known SNPs")
        p.add_argument(
            "-known_recalibration_table", default=None,
            help="npz with 'table' (u8[n_rg, qual, cycle, dinuc]) and "
            "'gl' — apply this pre-solved recalibration table instead "
            "of solving one at barrier 2 (the known-sites workflow; a "
            "previous run's --run-dir table sidecar is directly "
            "reusable).  Arms the fused B→C megakernel tier "
            "(docs/PERF.md); -streaming only",
        )
        p.add_argument("-realign_indels", action="store_true")
        p.add_argument("-known_indels", default=None,
                       help="VCF of known INDELs; without it the consensus-from-reads model is used")
        p.add_argument("-max_indel_size", type=int, default=500)
        p.add_argument("-max_consensus_number", type=int, default=30)
        p.add_argument("-log_odds_threshold", type=float, default=5.0)
        p.add_argument("-max_target_size", type=int, default=3000)
        p.add_argument("-trimReads", action="store_true")
        p.add_argument("-trimFromStart", type=int, default=0)
        p.add_argument("-trimFromEnd", type=int, default=0)
        p.add_argument("-trimReadGroup", default=None)
        p.add_argument("-qualityBasedTrim", action="store_true")
        p.add_argument("-qualityThreshold", type=int, default=20)
        p.add_argument("-trimBeforeBQSR", action="store_true")
        p.add_argument(
            "-repartition", type=int, default=-1,
            help="no-op: columnar batches have no RDD partition count; "
            "sharding is chosen by the device mesh (logged when set)",
        )
        p.add_argument(
            "-coalesce", type=int, default=-1,
            help="no-op: columnar batches have no RDD partition count; "
            "sharding is chosen by the device mesh (logged when set)",
        )
        p.add_argument("-sort_fastq_output", action="store_true")
        p.add_argument(
            "-checkpoint_dir", default=None,
            help="materialize each completed stage to Parquet here and "
            "resume after the deepest completed stage on rerun (the "
            "framework's failure-recovery story: stage checkpoint-restart "
            "over re-shardable columnar stores)",
        )
        p.add_argument(
            "--report", dest="report", default=None, metavar="PATH",
            help="write the analyzer run report (per-device busy/idle "
            "attribution, barrier decomposition, critical path, latency "
            "quantiles — the 'adam-tpu analyze' view of this run) to "
            "PATH on completion; -streaming only",
        )
        p.add_argument(
            "-streaming", action="store_true",
            help="run the transform as the streamed, overlapped windowed "
            "pipeline (ingest || device kernels || part-file writes; "
            "pipelines/streamed.py) — output becomes a Parquet part-file "
            "directory; requires a markdup/BQSR/realign stage set",
        )
        p.add_argument(
            "-window_reads", type=int, default=262_144,
            help="ingest window size in reads for -streaming — the unit "
            "of overlap, device round-robin and durable resume",
        )
        p.add_argument(
            "--run-dir", dest="run_dir", default=None, metavar="DIR",
            help="durable window-granular resume journal for the "
            "-streaming pipeline (docs/ROBUSTNESS.md): records each "
            "output window as complete after its part's atomic+fsync'd "
            "publish and persists observe-histogram/recalibration-table "
            "sidecars, so a killed run can resume instead of restarting",
        )
        p.add_argument(
            "--resume", dest="resume", action="store_true",
            help="resume a killed -streaming run from --run-dir's "
            "journal: completed windows are skipped, output stays "
            "bit-identical to an uninterrupted run; a journal recorded "
            "for different input bytes, flags or window plan is refused "
            "with a clean restart (never mixed output)",
        )
        p.add_argument(
            "-shards", type=int, default=0,
            help="run as the composed out-of-core sharded pipeline over N "
            "genome-bin shards (parallel/sharded.py): windowed ingest "
            "shuffles to 5'-clipped-position bins, per-shard passes with "
            "global duplicate/target barriers, boundary-correct realign "
            "tail — the one-host embodiment of the multi-host execution "
            "shape; supports the markdup/BQSR/realign stage set on "
            "SAM/BAM input",
        )
        p.add_argument(
            "-backend", default="tpu", choices=["tpu", "spark"],
            help="execution backend: 'tpu' runs the pipeline here; "
            "'spark' is the embedding mode — the caller (a Spark "
            "mapPartitions closure) ships Arrow record batches through "
            "AlignmentDataset.from_arrow/to_arrow and this process acts "
            "as the per-partition kernel executor",
        )
        p.add_argument("-force_load_bam", action="store_true")
        p.add_argument("-force_load_fastq", action="store_true")
        p.add_argument("-force_load_ifastq", action="store_true")
        p.add_argument("-force_load_parquet", action="store_true")

    @classmethod
    def run(cls, args):
        from adam_tpu.api.datasets import GenotypeDataset
        from adam_tpu.io import context

        if args.backend == "spark":
            # embedding mode: this process is the per-partition executor —
            # the Spark driver pipes Arrow IPC partition batches through
            # stdin/stdout (AlignmentDataset.from_arrow -> stages ->
            # to_arrow); file paths are ignored (pass "-" "-")
            from adam_tpu.api.datasets import GenotypeDataset as _GD
            from adam_tpu.api.spark_executor import StageConfig, serve

            cfg = StageConfig(
                mark_duplicates=bool(args.mark_duplicate_reads),
                recalibrate=bool(args.recalibrate_base_qualities),
                realign=bool(args.realign_indels),
            )
            if args.known_snps:
                cfg.known_snps = _GD.load(args.known_snps).snp_table()
            if args.known_indels:
                cfg.known_indels = _GD.load(args.known_indels).indel_table()
            import logging

            served = serve(cfg)
            logging.getLogger(__name__).info(
                "spark executor drained: %d partitions", served
            )
            return 0

        # the observability sinks only the -streaming pipeline produces:
        # warn up front (covers -shards AND the plain path) instead of
        # exiting 0 with a silently missing artifact — main() already
        # enabled recording for --report, so the mistake costs real time
        if getattr(args, "report", None) and not args.streaming:
            print(
                "transform: --report is only produced by the -streaming "
                f"pipeline; {args.report} will not be written (use "
                "--metrics-json/--trace-out + 'adam-tpu analyze' for "
                "other modes)",
                file=sys.stderr,
            )
        if getattr(args, "progress", None) and not args.streaming:
            print(
                "transform: --progress heartbeat is emitted by the "
                "-streaming pipeline only; no lines will be written",
                file=sys.stderr,
            )
        if getattr(args, "resume", None) and not getattr(args, "run_dir",
                                                         None):
            print(
                "transform: --resume needs the journal directory; pass "
                "--run-dir DIR (the same DIR the killed run journaled "
                "into)",
                file=sys.stderr,
            )
            return 2
        if getattr(args, "run_dir", None) and not args.streaming:
            print(
                "transform: --run-dir/--resume journal the -streaming "
                "pipeline only; use -checkpoint_dir for the composed "
                "stage pipeline",
                file=sys.stderr,
            )
            return 2
        if args.shards and args.shards < 0:
            print(f"transform -shards must be positive (got {args.shards})",
                  file=sys.stderr)
            return 2
        if args.window_reads < 1:
            print(
                f"transform -window_reads must be positive (got "
                f"{args.window_reads})", file=sys.stderr,
            )
            return 2
        if args.shards and args.streaming:
            print(
                "transform -shards and -streaming are mutually exclusive "
                "execution modes; pass one or the other",
                file=sys.stderr,
            )
            return 2
        if args.shards or args.streaming:
            # windowed execution modes share validation and knowns/tuning
            # plumbing: -shards N routes through the composed sharded
            # pipeline, -streaming through the overlapped windowed one
            mode = "-shards" if args.shards else "-streaming"
            ok_stages = not (
                args.trimReads or args.qualityBasedTrim or args.sort_reads
            )
            base = str(args.input)
            if base.endswith(".gz"):
                base = base[:-3]
            if (
                not ok_stages
                or not base.endswith((".sam", ".bam"))
                or args.force_load_fastq
                or args.force_load_ifastq
                or args.force_load_parquet
            ):
                print(
                    f"transform {mode} supports the markdup/BQSR/realign "
                    "stage set on windowed SAM/BAM input; drop it for "
                    "trim/sort pipelines or other formats",
                    file=sys.stderr,
                )
                return 2
            from adam_tpu.api.datasets import GenotypeDataset as _GD

            known = indels = None
            contig_names = None
            if args.known_snps or args.known_indels:
                contig_names = context.load_header(args.input).seq_dict.names
            if args.known_snps:
                known = _GD.load(
                    args.known_snps, contig_names=contig_names
                ).snp_table()
            if args.known_indels:
                indels = _GD.load(
                    args.known_indels, contig_names=contig_names
                ).indel_table()
            kw = dict(
                mark_duplicates=bool(args.mark_duplicate_reads),
                recalibrate=bool(args.recalibrate_base_qualities),
                realign=bool(args.realign_indels),
                known_snps=known,
                known_indels=indels,
                compression=args.parquet_compression_codec,
                max_indel_size=args.max_indel_size,
                max_consensus_number=args.max_consensus_number,
                lod_threshold=args.log_odds_threshold,
                max_target_size=args.max_target_size,
                dump_observations=args.dump_observations,
            )
            if mode == "-shards":
                from adam_tpu.parallel.sharded import transform_sharded

                transform_sharded(
                    args.input, args.output, args.shards, **kw
                )
            else:
                from adam_tpu.pipelines.streamed import transform_streamed

                if getattr(args, "report", None):
                    # pre-flight the report path BEFORE the (potentially
                    # hours-long) run: a typo'd directory must fail in
                    # milliseconds, not after the pipeline finishes
                    try:
                        with open(args.report, "a"):
                            pass
                    except OSError as e:
                        print(f"transform: cannot write --report "
                              f"{args.report}: {e}", file=sys.stderr)
                        return 2
                known_tbl = None
                if getattr(args, "known_recalibration_table", None):
                    import numpy as _np

                    with _np.load(args.known_recalibration_table) as z:
                        known_tbl = (
                            _np.asarray(z["table"], _np.uint8),
                            int(z["gl"]),
                        )
                transform_streamed(
                    args.input, args.output,
                    window_reads=args.window_reads,
                    devices=getattr(args, "devices", None),
                    partitioner=getattr(args, "partitioner", None),
                    progress=getattr(args, "progress", None),
                    run_dir=getattr(args, "run_dir", None),
                    resume=bool(getattr(args, "resume", False)),
                    known_table=known_tbl, **kw,
                )
                if getattr(args, "report", None):
                    # the analyzer view of THIS run: trace-grade (gap
                    # analysis + critical path) — main() enabled
                    # recording because --report was passed, so the
                    # global TRACE holds the absorbed run events
                    from adam_tpu.utils import analyzer
                    from adam_tpu.utils import telemetry as tele

                    report = analyzer.analyze(tele.TRACE.to_chrome_trace())
                    try:
                        with open(args.report, "w") as fh:
                            fh.write(analyzer.render_report(report) + "\n")
                    except OSError as e:
                        # the dataset is already written and valid: a
                        # report-write failure (disk filled mid-run)
                        # must not turn success into a crash
                        print(f"transform: report write to "
                              f"{args.report} failed: {e}",
                              file=sys.stderr)
            return 0

        with ins.TIMERS.time(ins.LOAD_ALIGNMENTS):
            if args.force_load_bam:
                ds = context.load_bam(args.input)
            elif args.force_load_fastq:
                ds = context.load_fastq(args.input)
            elif args.force_load_ifastq:
                ds = context.load_interleaved_fastq(
                    args.input, stringency=args.stringency
                )
            elif args.force_load_parquet:
                ds = context.load_parquet_alignments(args.input)
            else:
                ds = context.load_alignments(
                    args.input, stringency=args.stringency
                )

        if args.repartition != -1 or args.coalesce != -1:
            import logging

            logging.getLogger(__name__).warning(
                "-repartition/-coalesce are no-ops here: columnar batches "
                "have no RDD partition count (sharding follows the device "
                "mesh)"
            )

        stages = []

        if args.trimReads:
            def _trim(ds):
                with ins.TIMERS.time(ins.TRIM_READS):
                    rg_idx = None
                    if args.trimReadGroup is not None:
                        rg_idx = ds.header.read_groups.names.index(
                            args.trimReadGroup
                        )
                    from adam_tpu.pipelines import trim

                    return trim.trim_reads(
                        ds, args.trimFromStart, args.trimFromEnd, rg_idx=rg_idx
                    )
            stages.append(("trim", _trim))

        if args.qualityBasedTrim and args.trimBeforeBQSR:
            stages.append((
                "quality_trim",
                lambda ds: ds.trim_low_quality_read_groups(
                    args.qualityThreshold
                ),
            ))

        if args.mark_duplicate_reads:
            def _markdup(ds):
                with ins.TIMERS.time(ins.MARK_DUPLICATES):
                    return ds.mark_duplicates()
            stages.append(("mark_duplicates", _markdup))

        if args.realign_indels:
            def _realign(ds):
                with ins.TIMERS.time(ins.REALIGN_INDELS):
                    kw = dict(
                        max_indel_size=args.max_indel_size,
                        max_consensus_number=args.max_consensus_number,
                        lod_threshold=args.log_odds_threshold,
                        max_target_size=args.max_target_size,
                    )
                    if args.known_indels:
                        gt = GenotypeDataset.load(
                            args.known_indels, contig_names=ds.seq_dict.names
                        )
                        return ds.realign_indels(
                            consensus_model="knowns",
                            known_indels=gt.indel_table(), **kw,
                        )
                    return ds.realign_indels(consensus_model="reads", **kw)
            stages.append(("realign_indels", _realign))

        if args.recalibrate_base_qualities:
            def _bqsr(ds):
                with ins.TIMERS.time(ins.BQSR):
                    known = None
                    if args.known_snps:
                        gt = GenotypeDataset.load(
                            args.known_snps, contig_names=ds.seq_dict.names
                        )
                        known = gt.snp_table()
                    return ds.recalibrate_base_qualities(
                        known_snps=known,
                        dump_observation_table=args.dump_observations,
                    )
            stages.append(("bqsr", _bqsr))

        if args.qualityBasedTrim and not args.trimBeforeBQSR:
            stages.append((
                "quality_trim",
                lambda ds: ds.trim_low_quality_read_groups(
                    args.qualityThreshold
                ),
            ))

        if args.sort_reads:
            def _sort(ds):
                with ins.TIMERS.time(ins.SORT_READS):
                    return ds.sort_by_reference_position()
            stages.append(("sort", _sort))

        from adam_tpu.pipelines.checkpoint import (
            compose_fingerprint,
            input_fingerprint,
            run_stages,
        )

        fp = None
        if args.checkpoint_dir:
            # input content identity + every stage-affecting flag value:
            # a rerun over different bytes (or retuned knobs) must
            # invalidate the stage stores instead of silently reloading
            # them (the stage list alone only catches REORDERED flags)
            fp = compose_fingerprint({
                "input": input_fingerprint(args.input),
                "trimFromStart": args.trimFromStart,
                "trimFromEnd": args.trimFromEnd,
                "trimReadGroup": args.trimReadGroup,
                "qualityThreshold": args.qualityThreshold,
                # known-sites files fingerprint by CONTENT, not path:
                # editing sites in place must invalidate the stores
                "known_snps": (
                    input_fingerprint(args.known_snps)
                    if args.known_snps else None
                ),
                "known_indels": (
                    input_fingerprint(args.known_indels)
                    if args.known_indels else None
                ),
                "max_indel_size": args.max_indel_size,
                "max_consensus_number": args.max_consensus_number,
                "log_odds_threshold": args.log_odds_threshold,
                "max_target_size": args.max_target_size,
            })
        ds = run_stages(ds, stages, checkpoint_dir=args.checkpoint_dir,
                        fingerprint=fp)

        with ins.TIMERS.time(ins.SAVE_OUTPUT):
            if args.sort_fastq_output and str(args.output).endswith(
                (".fq", ".fastq")
            ):
                # adamSaveAsFastq(sort=true): name-sorted FASTQ export
                import numpy as np

                from adam_tpu.formats.strings import StringColumn

                names = StringColumn.of(ds.sidecar.names).to_fixed_bytes()
                order = np.argsort(names, kind="stable")
                ds = ds.take_rows(order)
            ds.save(args.output, compression=args.parquet_compression_codec)
        return 0


class Serve(Command):
    """Multi-job transform service (adam_tpu/serve; docs/ROBUSTNESS.md
    "Fault-isolated multi-job scheduling"): run N concurrent streamed
    transform jobs on one shared device pool with admission control,
    per-tenant weighted fairness, job quarantine, graceful SIGTERM
    drain and whole-process crash recovery from the run-root."""

    name = "serve"
    description = ("Run concurrent streamed transform jobs on a shared "
                   "device pool (bounded slots, per-tenant fairness, "
                   "quarantine, graceful drain, crash recovery)")

    @classmethod
    def configure(cls, p):
        p.add_argument(
            "run_root", metavar="RUN_ROOT",
            help="durable service state root: one subdirectory per job "
            "(JOB.json + run/ journal + heartbeat.ndjson); on startup "
            "every incomplete job found here resumes bit-identically "
            "from its journal",
        )
        p.add_argument(
            "--jobs", dest="jobs", default=None, metavar="FILE",
            help="JSON manifest of jobs to submit (see "
            "adam_tpu/api/transform_service.py for the format); jobs "
            "already tracked in RUN_ROOT are skipped, so re-running "
            "the same command after a crash only resumes",
        )
        p.add_argument(
            "--max-jobs", dest="max_jobs", type=int, default=2,
            metavar="N",
            help="bounded job slots: submissions beyond N receive a "
            "typed Busy rejection (the CLI's own manifest loop polls "
            "until a slot frees; default 2)",
        )
        p.add_argument(
            "--job-retries", dest="job_retries", type=int, default=None,
            metavar="N",
            help="resume a failing job from its journal N times before "
            "quarantining it (default ADAM_TPU_SCHED_JOB_RETRIES or 1; "
            "quarantine frees the job's slot and devices, its journal "
            "stays resumable, surviving jobs are untouched)",
        )
        p.add_argument(
            "--batch", dest="batch", action="store_true", default=None,
            help="continuous cross-job window batching "
            "(serve/batching.py, docs/SERVING.md): concurrent jobs' "
            "windows merge into one fused device dispatch per pass, "
            "WFQ-ordered, with a bounded coalescing delay "
            "(ADAM_TPU_BATCH_WAIT_MS, default 25 ms); every job's "
            "output stays byte-identical to its solo run.  Default: "
            "ADAM_TPU_BATCH, off",
        )
        p.add_argument(
            "--quota", dest="quota", default=None, metavar="SPEC",
            help="per-tenant rolling-window budgets, e.g. "
            "'tenantA:bytes=512M,compute=10s;*:bytes=1G' (window "
            "ADAM_TPU_QUOTA_WINDOW_S, default 60 s): an over-budget "
            "tenant's submissions are refused with a typed quota "
            "rejection (HTTP 429 on the gateway) carrying a "
            "budget-derived Retry-After; other tenants are untouched.  "
            "Default: ADAM_TPU_QUOTA, none",
        )
        p.add_argument(
            "--slo", dest="slo", default=None, metavar="SPEC",
            help="declarative service-level objectives, e.g. "
            "'tenantA:p99(sched.job.run)<30s;*:avail>=0.99' "
            "(utils/slo.py, docs/OBSERVABILITY.md): per-tenant or "
            "service-wide (*) latency/availability/throughput "
            "objectives judged over rolling windows "
            "(ADAM_TPU_SLO_WINDOW_S, default 300 s short / 12x long); "
            "error-budget state persists in RUN_ROOT/SLO_BUDGET.json, "
            "a corroborated fast burn fires an slo.burn incident "
            "bundle, and GET /slo + /metrics expose compliance and "
            "burn.  Default: ADAM_TPU_SLO, none",
        )
        p.add_argument(
            "--listen", dest="listen", default=None, metavar="HOST:PORT",
            help="serve the HTTP gateway on HOST:PORT (port 0 = OS-"
            "assigned; the bound address publishes durably to "
            "RUN_ROOT/gateway.json): idempotency-keyed PUT submission, "
            "typed 429/503 back-pressure with Retry-After, NDJSON "
            "heartbeat streaming, Range-resumable part fetch "
            "(docs/SERVING.md).  The process then runs until SIGTERM, "
            "which drains gracefully: stop accepting -> 503 -> "
            "scheduler drain -> every journal settled -> exit 0",
        )

    @classmethod
    def run(cls, args):
        import signal
        import threading
        import time as time_mod
        from collections import deque

        from adam_tpu.api.transform_service import (
            TransformService,
            load_jobs_manifest,
        )
        from adam_tpu.serve.job import Admitted

        specs = []
        if args.jobs:
            try:
                specs = load_jobs_manifest(args.jobs)
            except (OSError, ValueError) as e:
                print(f"serve: {e}", file=sys.stderr)
                return 2
        listen = None
        if args.listen:
            from adam_tpu.gateway.protocol import parse_listen

            try:
                listen = parse_listen(args.listen)
            except ValueError as e:
                print(f"serve: {e}", file=sys.stderr)
                return 2
        svc = TransformService(
            args.run_root,
            max_jobs=args.max_jobs,
            devices=getattr(args, "devices", None),
            partitioner=getattr(args, "partitioner", None),
            job_retries=args.job_retries,
            batching=args.batch,
            quota=args.quota,
            slo=args.slo,
        )
        gw = None
        if listen is not None:
            from adam_tpu.gateway.server import GatewayServer

            gw = GatewayServer(svc, *listen)
            gw.start()
            print(f"serve: gateway listening on {gw.url} "
                  f"(discovery: {args.run_root}/gateway.json)")
        # SIGTERM/SIGINT = graceful drain: the handler only flips an
        # event (signal-safe); the submission loop below performs the
        # actual drain — admissions stop, every job finishes its
        # in-flight windows, fsyncs its journal, and we exit 0
        drain_req = threading.Event()
        prev_handlers = {}
        for sig in (signal.SIGTERM, signal.SIGINT):
            try:
                prev_handlers[sig] = signal.signal(
                    sig, lambda _s, _f: drain_req.set()
                )
            except (ValueError, OSError):  # non-main thread / platform
                pass
        drained = False
        try:
            recovered = svc.recover()
            if recovered:
                print(f"serve: resumed {len(recovered)} incomplete "
                      f"job(s) from {args.run_root}: "
                      f"{', '.join(recovered)}")
            tracked = set(svc.status()["jobs"])
            pending = deque(s for s in specs if s.job_id not in tracked)
            skipped = len(specs) - len(pending)
            if skipped:
                print(f"serve: {skipped} manifest job(s) already "
                      "tracked in the run root; not resubmitting")
            while True:
                if drain_req.is_set() and not drained:
                    # drain ordering (docs/SERVING.md): the gateway
                    # stops accepting FIRST (new submissions bounce
                    # with a typed 503 while live event streams and
                    # part fetches keep flowing), then the scheduler
                    # drains every lane to a window boundary
                    if gw is not None:
                        gw.stop_accepting()
                    svc.request_drain()
                    drained = True
                    pending.clear()
                # has_capacity gates the poll so waiting for a slot
                # doesn't count one sched.jobs.rejected per tick
                if pending and svc.scheduler.has_capacity():
                    got = svc.submit(pending[0])
                    if isinstance(got, Admitted):
                        print(f"serve: admitted {got.job_id}")
                        pending.popleft()
                        continue
                    if got.kind != "capacity":
                        print(f"serve: {pending[0].job_id} refused "
                              f"({got.reason})", file=sys.stderr)
                        pending.popleft()
                        continue
                    # lost a capacity race: poll for a freed slot below
                if not pending and svc.wait(timeout=0.25):
                    # a gateway keeps the service alive for remote
                    # submissions until a drain is requested — idle is
                    # the steady state, not the exit condition, and it
                    # must BLOCK (on the drain signal, for a prompt
                    # SIGTERM response), not spin through instant
                    # wait() returns
                    if gw is None or drained:
                        break
                    drain_req.wait(timeout=0.25)
                if pending:
                    time_mod.sleep(0.1)
        finally:
            for sig, h in prev_handlers.items():
                try:
                    signal.signal(sig, h)
                except (ValueError, OSError):
                    pass
            # settled before the listener dies: close() ends event
            # streams only after every JOB.json above is durable
            if gw is not None:
                gw.close()
            svc.close()
        status = svc.status()
        bad = 0
        for jid, view in sorted(status["jobs"].items()):
            line = f"serve: job {jid}: {view['state']}"
            if view.get("windows_durable"):
                # parts, not windows: the realign tail part rides past
                # the window plan, so the count can exceed n_windows
                line += f" ({view['windows_durable']} durable part(s))"
            if view.get("error"):
                line += f" — {view['error']}"
            print(line)
            if view["state"] == "quarantined":
                bad += 1
        if drained:
            print("serve: drained cleanly (journals durable; rerun "
                  "this command to resume)")
            return 0
        return 1 if bad else 0


class Adam2Fastq(Command):
    """Export reads to FASTQ, optionally splitting pairs
    (Adam2Fastq.scala:25-80)."""

    name = "adam2fastq"
    description = "Convert BAM to FASTQ files"

    @classmethod
    def configure(cls, p):
        p.add_argument("input", metavar="INPUT")
        p.add_argument("output", metavar="OUTPUT")
        p.add_argument("output2", metavar="OUTPUT2", nargs="?", default=None,
                       help="all second-in-pair reads go here, if provided")
        p.add_argument("-no-projection", dest="no_projection",
                       action="store_true")
        p.add_argument("-repartition", type=int, default=-1)

    @classmethod
    def run(cls, args):
        from adam_tpu.io import context

        kw = {}
        if not args.no_projection and str(args.input).endswith(
            (".adam", ".parquet")
        ):
            kw["projection"] = ["readName", "sequence", "qual", "flags"]
        ds = context.load_alignments(args.input, **kw)
        if args.output2:
            ds.save_paired_fastq(
                args.output, args.output2, stringency=args.stringency
            )
        else:
            from adam_tpu.io import fastq

            fastq.write_fastq(args.output, ds.batch, ds.sidecar)
        return 0


class PluginExecutor(Command):
    """Load and run a user plugin (PluginExecutor.scala:41-125)."""

    name = "plugin"
    description = "Executes an AdamPlugin"

    @classmethod
    def configure(cls, p):
        p.add_argument("plugin", metavar="PLUGIN",
                       help="dotted path of the AdamPlugin to run")
        p.add_argument("input", metavar="INPUT")
        p.add_argument("-access_control", default=None,
                       help="dotted path of an AccessControl class")
        p.add_argument("-plugin_args", default="",
                       help="string of args passed to the plugin, split on spaces")

    @classmethod
    def run(cls, args):
        from adam_tpu import plugins

        plugin = plugins.load_plugin(args.plugin)
        ac = None
        if args.access_control:
            cls_ = plugins.load_plugin(args.access_control,
                                       base=plugins.AccessControl)
            ac = cls_
        out = plugins.execute_plugin(
            plugin, args.input, args.plugin_args.split(), ac
        )
        if out is not None:
            for row in out:
                print(row)
        return 0


class Flatten(Command):
    """Flatten nested Parquet columns for SQL engines
    (Flatten.scala:32-90 + util/Flattener.scala)."""

    name = "flatten"
    description = ("Convert a ADAM format file to a version with a flattened "
                   "schema, suitable for querying with tools like Impala")

    @classmethod
    def configure(cls, p):
        p.add_argument("input", metavar="INPUT")
        p.add_argument("output", metavar="OUTPUT")

    @classmethod
    def run(cls, args):
        from adam_tpu.utils.flattener import flatten_parquet

        flatten_parquet(args.input, args.output,
                        compression=args.parquet_compression_codec)
        return 0


class _GatewayCommand(Command):
    """Shared plumbing for the remote-client verbs: URL resolution
    (a gateway URL or a serve run-root with gateway.json) and the
    connection-error -> exit-2 convention."""

    @staticmethod
    def client(args):
        from adam_tpu.gateway.client import GatewayClient, resolve_url

        return GatewayClient(resolve_url(args.url))

    @staticmethod
    def add_url(p):
        p.add_argument(
            "url", metavar="URL|RUN_ROOT",
            help="gateway address (http://host:port) or a serve "
            "run-root directory carrying gateway.json (written by "
            "'adam-tpu serve --listen')",
        )


class Submit(_GatewayCommand):
    """Remote job submission over the HTTP gateway (adam_tpu/gateway;
    docs/SERVING.md): idempotency-keyed PUTs, duplicate-safe across
    client retries and gateway restarts, honoring 429/503 Retry-After
    with the retry policy's seeded-jitter backoff."""

    name = "submit"
    description = ("Submit transform jobs to a running adam-tpu "
                   "gateway over HTTP (idempotent, back-pressure "
                   "aware)")

    @classmethod
    def configure(cls, p):
        cls.add_url(p)
        p.add_argument(
            "--jobs", dest="jobs", required=True, metavar="FILE",
            help="JSON jobs manifest (the 'adam-tpu serve --jobs' "
            "format; see adam_tpu/api/transform_service.py)",
        )
        p.add_argument(
            "--deadline", dest="deadline", type=float, default=None,
            metavar="S",
            help="give up on back-pressured submissions after S "
            "seconds (default: wait as long as the gateway says to)",
        )
        p.add_argument(
            "--wait", dest="wait", action="store_true",
            help="after submitting, poll until every job reaches a "
            "terminal state (exit 1 if any quarantined)",
        )

    @classmethod
    def run(cls, args):
        from adam_tpu.api.transform_service import load_jobs_manifest
        from adam_tpu.gateway.client import GatewayBusy, GatewayError

        try:
            specs = load_jobs_manifest(args.jobs)
        except (OSError, ValueError) as e:
            print(f"submit: {e}", file=sys.stderr)
            return 2
        try:
            client = cls.client(args)
        except ValueError as e:
            print(f"submit: {e}", file=sys.stderr)
            return 2
        try:
            for spec in specs:
                got = client.submit_with_retry(
                    spec.job_id, spec.to_doc(),
                    deadline_s=args.deadline,
                )
                state = got.get("state", "?")
                dup = " (already submitted)" if got.get("duplicate") \
                    else ""
                print(f"submit: {spec.job_id}: {state}{dup}")
        except GatewayBusy as e:
            print(f"submit: {e}", file=sys.stderr)
            return 1
        except (GatewayError, OSError) as e:
            print(f"submit: {e}", file=sys.stderr)
            return 2
        if not args.wait:
            return 0
        bad = 0
        try:
            for spec in specs:
                view = client.wait(spec.job_id)
                print(f"submit: {spec.job_id} -> {view['state']}")
                if view["state"] == "quarantined":
                    bad += 1
        except (GatewayError, OSError) as e:
            print(f"submit: {e}", file=sys.stderr)
            return 2
        return 1 if bad else 0


class ServiceStatus(_GatewayCommand):
    """Point-in-time service (or per-job) status over the gateway."""

    name = "status"
    description = ("Print a running adam-tpu gateway's service status "
                   "(or one job's) as JSON")

    @classmethod
    def configure(cls, p):
        cls.add_url(p)
        p.add_argument("job", metavar="JOB", nargs="?", default=None,
                       help="one job id (default: the whole service)")

    @classmethod
    def run(cls, args):
        import json

        from adam_tpu.gateway.client import GatewayError

        try:
            doc = cls.client(args).status(args.job)
        except ValueError as e:
            print(f"status: {e}", file=sys.stderr)
            return 2
        except (GatewayError, OSError) as e:
            print(f"status: {e}", file=sys.stderr)
            return 2
        print(json.dumps(doc, indent=1, default=str))
        return 0


class FetchResults(_GatewayCommand):
    """Byte-exact result download over the gateway: every published
    part of the job, sha256-verified, Range-resumable — a fetch
    SIGKILLed mid-download reruns and completes from where it died
    (docs/SERVING.md resumable-fetch semantics)."""

    name = "fetch"
    description = ("Download a job's output parts from a gateway "
                   "(sha256-verified, Range-resumable)")

    @classmethod
    def configure(cls, p):
        cls.add_url(p)
        p.add_argument("job", metavar="JOB")
        p.add_argument("dest", metavar="DEST_DIR",
                       help="local directory the parts land in")

    @classmethod
    def run(cls, args):
        from adam_tpu.gateway.client import GatewayError

        try:
            client = cls.client(args)
            fetched = client.fetch(args.job, args.dest)
        except ValueError as e:
            print(f"fetch: {e}", file=sys.stderr)
            return 2
        except GatewayError as e:
            print(f"fetch: {e}", file=sys.stderr)
            return 1
        except OSError as e:
            print(f"fetch: {e}", file=sys.stderr)
            return 2
        for name in sorted(fetched):
            print(f"fetch: {name} -> {fetched[name]} (sha256 verified)")
        if not fetched:
            print(f"fetch: job {args.job!r} has no published parts yet",
                  file=sys.stderr)
            return 1
        return 0


class CancelJob(_GatewayCommand):
    """Cancel one running job at its next window boundary: in-flight
    parts publish, the journal stays durable and resumable, the job
    lands 'interrupted' (a re-submission resumes it)."""

    name = "cancel"
    description = ("Cancel a running job on a gateway at its next "
                   "window boundary (journal stays resumable)")

    @classmethod
    def configure(cls, p):
        cls.add_url(p)
        p.add_argument("job", metavar="JOB")

    @classmethod
    def run(cls, args):
        from adam_tpu.gateway.client import GatewayError

        try:
            doc = cls.client(args).cancel(args.job)
        except ValueError as e:
            print(f"cancel: {e}", file=sys.stderr)
            return 2
        except GatewayError as e:
            print(f"cancel: {e}", file=sys.stderr)
            return 1
        except OSError as e:
            print(f"cancel: {e}", file=sys.stderr)
            return 2
        print(f"cancel: {doc.get('job_id')} cancelling (stops at its "
              "next window boundary; journal stays resumable)")
        return 0


COMMANDS = [
    CalculateDepth,
    CountReadKmers,
    CountContigKmers,
    Transform,
    Serve,
    Submit,
    ServiceStatus,
    FetchResults,
    CancelJob,
    Adam2Fastq,
    PluginExecutor,
    Flatten,
]
