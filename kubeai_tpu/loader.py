"""Model loader — stages weights from a source URL into a destination dir.

The TPU-native counterpart of the reference's model-loader container
(ref: components/model-loader/load.sh:20-67 + Dockerfile: a bash script
over huggingface-cli/awscli/gcloud/ossutil). Used by cache loader Jobs
and the adapter loader sidecar.

    python -m kubeai_tpu.loader <src-url> <dest-dir>
    python -m kubeai_tpu.loader --evict <dir>
    python -m kubeai_tpu.loader --warm-compile-cache <src-url> <dest-dir> [engine args...]

Schemes: file:// and pvc:// copy locally; hf:// uses huggingface_hub;
s3:// gs:// oss:// shell out to their CLIs when present. Destination is
written atomically (tmp dir + rename) so a crashed load never looks
complete.

--warm-compile-cache additionally AOT-compiles the engine's step
functions against the staged checkpoint's shapes (config.json +
tokenizer only — no weights are loaded) into the shared
JAX_COMPILATION_CACHE_DIR, so the cache is hot BEFORE the first replica
ever starts. Trailing engine-server args (e.g. the Model's spec.args:
``--max-seq-len 512 --max-slots 4``) pin the warmed shapes to what the
serving pods will actually run.
"""

from __future__ import annotations

import argparse
import os
import shutil
import subprocess
import sys
import tempfile

from kubeai_tpu.controller.model_source import parse_model_source
from kubeai_tpu.obs.logs import get_logger, setup_logging

log = get_logger("kubeai_tpu.loader")


def _atomic_dest(dest: str):
    os.makedirs(os.path.dirname(dest.rstrip("/")) or "/", exist_ok=True)
    return tempfile.mkdtemp(prefix=os.path.basename(dest.rstrip("/")) + ".tmp.", dir=os.path.dirname(dest.rstrip("/")))


def load(src_url: str, dest: str) -> None:
    src = parse_model_source(src_url)
    if os.path.isdir(dest) and os.listdir(dest):
        log.info("destination %s already populated; nothing to do", dest)
        return
    tmp = _atomic_dest(dest)
    try:
        if src.scheme in ("file", "pvc"):
            source_dir = src.local_path if src.scheme == "file" else f"/model/{src.pvc_subpath}"
            shutil.copytree(source_dir, tmp, dirs_exist_ok=True)
        elif src.scheme == "hf":
            from huggingface_hub import snapshot_download

            snapshot_download(repo_id=src.huggingface_repo, local_dir=tmp)
        elif src.scheme == "s3":
            subprocess.run(["aws", "s3", "sync", src.bucket_url, tmp], check=True)
        elif src.scheme == "gs":
            subprocess.run(["gcloud", "storage", "cp", "-r", src.bucket_url + "/*", tmp], check=True)
        elif src.scheme == "oss":
            subprocess.run(["ossutil", "cp", "-r", src.bucket_url, tmp], check=True)
        else:
            raise ValueError(f"loader does not support scheme {src.scheme!r}")
        if os.path.isdir(dest):
            shutil.rmtree(dest)
        os.rename(tmp, dest)
        tmp = None
        log.info("loaded %s -> %s", src_url, dest)
    finally:
        if tmp and os.path.isdir(tmp):
            shutil.rmtree(tmp, ignore_errors=True)


def evict(dest: str) -> None:
    if os.path.isdir(dest):
        shutil.rmtree(dest)
        log.info("evicted %s", dest)
    else:
        log.info("%s already absent", dest)


def stage_remote(url: str, base_dir: str, prefix: str = "") -> str:
    """Shared remote-source staging: file:// strips to a local path,
    other schemes (hf/s3/gs/oss) download into base_dir under a dest
    keyed by the URL hash — so a changed URL never reuses a stale
    download (load() skips already-populated destinations) — and plain
    paths pass through. Used by the engine server for models and by the
    engine itself for adapters (each gang rank stages independently)."""
    if url.startswith("file://"):
        return url[len("file://") :]
    if "://" in url:
        from kubeai_tpu.utils.xxh import xxh64

        dest = os.path.join(base_dir, f"{prefix}{xxh64(url) & 0xFFFFFFFFFFFF:012x}")
        load(url, dest)
        return dest
    return url


def warm_compile_cache(dest: str, engine_args: list[str] | None = None) -> dict | None:
    """Loader-side compile-cache warm: requires JAX_COMPILATION_CACHE_DIR
    (a cache placed from outside is one the serving pods share; warming
    this Job's own default would benefit nobody). Never raises — a warm
    failure must not fail the staging Job that gates pod creation."""
    from kubeai_tpu.engine.coldstart import setup_compile_cache, warm_from_checkpoint

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        log.info("JAX_COMPILATION_CACHE_DIR is not set; skipping compile warm")
        return None
    setup_compile_cache()
    try:
        stats = warm_from_checkpoint(dest, engine_args)
    except Exception as e:
        log.warning("compile warm failed (non-fatal): %s", e)
        return None
    log.info("warmed compile cache for %s: %s", dest, stats)
    return stats


def main(argv=None):
    parser = argparse.ArgumentParser("kubeai-tpu-loader")
    parser.add_argument("--evict", action="store_true")
    parser.add_argument(
        "--warm-compile-cache", action="store_true",
        help="after staging, AOT-compile the engine step functions for "
             "the checkpoint's shapes into JAX_COMPILATION_CACHE_DIR; "
             "trailing engine-server args pin the warmed shapes",
    )
    parser.add_argument("src_or_dir")
    parser.add_argument("dest", nargs="?")
    args, engine_args = parser.parse_known_args(argv)
    setup_logging("loader")
    if engine_args and not args.warm_compile_cache:
        # Trailing args are ONLY the warm step's engine flags; without
        # it they are typos (a misspelled --evict must not silently
        # turn into a staging run).
        parser.error(f"unrecognized arguments: {' '.join(engine_args)}")
    if args.evict:
        evict(args.src_or_dir)
    else:
        if not args.dest:
            parser.error("dest required")
        load(args.src_or_dir, args.dest)
        if args.warm_compile_cache:
            warm_compile_cache(args.dest, engine_args)


if __name__ == "__main__":
    sys.exit(main())
