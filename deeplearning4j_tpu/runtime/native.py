"""ctypes binding over the C++ PJRT runtime layer (native/).

ref: the JavaCPP presets (Nd4jCpu/Nd4jCuda generated JNI) that bound the JVM
to libnd4j's NativeOps C ABI (SURVEY §2.2). Here the native surface is
native/src/pjrt_runtime.cpp (PJRT C-API client: device enum, HBM buffers,
compile, execute) and the binding is ~200 lines of ctypes instead of 80k
lines of generated JNI — the per-op dispatch boundary the reference needed
is gone, so the ABI is just programs + buffers.

This layer is how a non-JAX host process (C++ service, another language)
would drive the framework's compiled StableHLO programs; the normal Python
path uses jax directly. It doubles as the runtime-substrate conformance
check (SURVEY §7.2 stage 0): tests compile a jax-exported module and compare
native execution against jax's own.
"""

from __future__ import annotations

import ctypes
import os
import pathlib
import subprocess
from typing import List, Optional, Sequence

import numpy as np

_REPO_ROOT = pathlib.Path(__file__).resolve().parents[2]
_NATIVE_DIR = _REPO_ROOT / "native"
_LIB_PATH = _NATIVE_DIR / "lib" / "libdl4j_tpu_runtime.so"


def default_plugin_path() -> Optional[str]:
    """The installed TPU PJRT plug-in (the ``libtpu`` wheel's .so), or None
    where it is not installed."""
    try:
        import libtpu
    except ImportError:
        return None
    path = libtpu.get_library_path()
    return path if os.path.exists(path) else None


# numpy dtype -> PJRT_Buffer_Type (xla/pjrt/c/pjrt_c_api.h enum order)
_PJRT_TYPE = {
    np.dtype(np.bool_): 1,   # PRED
    np.dtype(np.int8): 2,
    np.dtype(np.int16): 3,
    np.dtype(np.int32): 4,
    np.dtype(np.int64): 5,
    np.dtype(np.uint8): 6,
    np.dtype(np.uint16): 7,
    np.dtype(np.uint32): 8,
    np.dtype(np.uint64): 9,
    np.dtype(np.float16): 10,
    np.dtype(np.float32): 11,
    np.dtype(np.float64): 12,
    np.dtype(np.complex64): 14,
    np.dtype(np.complex128): 15,
}
_NUMPY_TYPE = {v: k for k, v in _PJRT_TYPE.items()}
_BF16 = 13  # surfaced as uint16 host-side (numpy has no bf16)


def ensure_built(force: bool = False) -> pathlib.Path:
    """Build native/lib/libdl4j_tpu_runtime.so (↔ running
    buildnativeoperations.sh before the JVM can load nd4j-native).

    Always consults ``make`` — make's own mtime comparison decides whether a
    rebuild is needed, so an edited pjrt_runtime.cpp can never be shadowed
    by a stale binary (r1 advisor finding)."""
    if force:
        subprocess.run(["make", "clean"], cwd=_NATIVE_DIR,
                       capture_output=True, text=True)
    proc = subprocess.run(["make"], cwd=_NATIVE_DIR,
                          capture_output=True, text=True)
    if proc.returncode != 0:
        if _LIB_PATH.exists():
            raise NativeRuntimeError(
                "native rebuild failed and a stale binary exists — refusing "
                f"to load it (exit {proc.returncode}):\n{proc.stderr}")
        raise NativeRuntimeError(
            f"native build failed (exit {proc.returncode}):\n{proc.stderr}")
    return _LIB_PATH


def default_compile_options() -> bytes:
    """Serialized CompileOptionsProto with 1 replica / 1 partition."""
    return make_compile_options()


def make_compile_options(num_replicas: int = 1, num_partitions: int = 1,
                         portable: bool = False) -> bytes:
    """Serialized CompileOptionsProto (↔ the reference's per-backend build
    flags). ``num_replicas``/``num_partitions`` request an SPMD executable
    spanning that many devices; ``portable`` compiles device-unassigned so
    ``execute(device=k)`` can target any addressable device at run time
    (PJRT portable-executable path)."""
    from jaxlib import xla_client

    opts = xla_client.CompileOptions()
    opts.num_replicas = num_replicas
    opts.num_partitions = num_partitions
    if num_partitions > 1:
        opts.executable_build_options.use_spmd_partitioning = True
    if portable:
        opts.compile_portable_executable = True
    opts.executable_build_options.num_replicas = num_replicas
    opts.executable_build_options.num_partitions = num_partitions
    return opts.SerializeAsString()


class NativeRuntimeError(RuntimeError):
    pass


class _Lib:
    _instance: Optional[ctypes.CDLL] = None

    @classmethod
    def get(cls) -> ctypes.CDLL:
        if cls._instance is None:
            lib = ctypes.CDLL(str(ensure_built()))
            c = ctypes.c_void_p
            lib.dl4j_pjrt_load.restype = c
            lib.dl4j_pjrt_load.argtypes = [
                ctypes.c_char_p, ctypes.POINTER(ctypes.c_char_p),
                ctypes.POINTER(ctypes.c_int), ctypes.POINTER(ctypes.c_char_p),
                ctypes.POINTER(ctypes.c_int64), ctypes.c_int,
                ctypes.c_char_p, ctypes.c_size_t]
            lib.dl4j_pjrt_destroy.argtypes = [c]
            lib.dl4j_pjrt_api_version.argtypes = [
                c, ctypes.POINTER(ctypes.c_int), ctypes.POINTER(ctypes.c_int)]
            lib.dl4j_pjrt_platform_name.argtypes = [c, ctypes.c_char_p,
                                                    ctypes.c_size_t]
            lib.dl4j_pjrt_device_count.argtypes = [c]
            lib.dl4j_pjrt_device_desc.argtypes = [c, ctypes.c_int,
                                                  ctypes.c_char_p, ctypes.c_size_t]
            lib.dl4j_pjrt_compile.restype = c
            lib.dl4j_pjrt_compile.argtypes = [
                c, ctypes.c_char_p, ctypes.c_size_t, ctypes.c_char_p,
                ctypes.c_char_p, ctypes.c_size_t, ctypes.c_char_p, ctypes.c_size_t]
            lib.dl4j_pjrt_exe_destroy.argtypes = [c, c]
            lib.dl4j_pjrt_exe_num_outputs.argtypes = [c, c, ctypes.c_char_p,
                                                      ctypes.c_size_t]
            lib.dl4j_pjrt_buffer_from_host.restype = c
            lib.dl4j_pjrt_buffer_from_host.argtypes = [
                c, ctypes.c_void_p, ctypes.c_int,
                ctypes.POINTER(ctypes.c_int64), ctypes.c_int, ctypes.c_int,
                ctypes.c_char_p, ctypes.c_size_t]
            lib.dl4j_pjrt_buffer_destroy.argtypes = [c, c]
            lib.dl4j_pjrt_buffer_type.argtypes = [c, c]
            lib.dl4j_pjrt_buffer_ndims.argtypes = [c, c]
            lib.dl4j_pjrt_buffer_dims.argtypes = [c, c,
                                                  ctypes.POINTER(ctypes.c_int64),
                                                  ctypes.c_int]
            lib.dl4j_pjrt_buffer_size_bytes.restype = ctypes.c_longlong
            lib.dl4j_pjrt_buffer_size_bytes.argtypes = [c, c, ctypes.c_char_p,
                                                        ctypes.c_size_t]
            lib.dl4j_pjrt_buffer_to_host.argtypes = [
                c, c, ctypes.c_void_p, ctypes.c_longlong, ctypes.c_char_p,
                ctypes.c_size_t]
            lib.dl4j_pjrt_execute.argtypes = [
                c, c, ctypes.POINTER(c), ctypes.c_int, ctypes.POINTER(c),
                ctypes.c_int, ctypes.c_int, ctypes.c_char_p, ctypes.c_size_t]
            cls._instance = lib
        return cls._instance


_ERRLEN = 4096


def _err_buf():
    return ctypes.create_string_buffer(_ERRLEN)


class NativeExecutable:
    """A loaded PJRT executable (↔ libnd4j registered graph handle)."""

    def __init__(self, runtime: "NativeRuntime", handle, portable: bool = False):
        self._rt = runtime
        self._handle = handle
        self.portable = portable
        err = _err_buf()
        n = self._rt._lib.dl4j_pjrt_exe_num_outputs(
            runtime._ctx, handle, err, _ERRLEN)
        if n < 0:
            raise NativeRuntimeError(err.value.decode())
        self.num_outputs = n

    def execute(self, args: Sequence[np.ndarray], device: int = 0) -> List[np.ndarray]:
        """Run on ``device`` (addressable-device index). Non-default devices
        need a portable executable (``compile(..., portable=True)``) — a
        device-assigned executable is pinned by its compile options."""
        if device != 0 and not self.portable:
            raise NativeRuntimeError(
                f"executable is device-assigned; compile(portable=True) to "
                f"execute on device {device}")
        if device < 0 or device >= self._rt.device_count():
            raise NativeRuntimeError(
                f"device {device} out of range 0..{self._rt.device_count()-1}")
        rt, lib = self._rt, self._rt._lib
        err = _err_buf()
        arg_handles = []
        try:
            for a in args:
                a = np.ascontiguousarray(a)
                dt = _PJRT_TYPE.get(a.dtype)
                if dt is None:
                    raise NativeRuntimeError(f"unsupported dtype {a.dtype}")
                dims = (ctypes.c_int64 * a.ndim)(*a.shape)
                h = lib.dl4j_pjrt_buffer_from_host(
                    rt._ctx, a.ctypes.data_as(ctypes.c_void_p), dt, dims,
                    a.ndim, device, err, _ERRLEN)
                if not h:
                    raise NativeRuntimeError(
                        f"buffer_from_host: {err.value.decode()}")
                arg_handles.append(h)

            in_arr = (ctypes.c_void_p * len(arg_handles))(*arg_handles)
            out_arr = (ctypes.c_void_p * self.num_outputs)()
            exec_device = device if self.portable else -1
            rc = lib.dl4j_pjrt_execute(
                rt._ctx, self._handle, in_arr, len(arg_handles), out_arr,
                self.num_outputs, exec_device, err, _ERRLEN)
            if rc != 0:
                raise NativeRuntimeError(f"execute: {err.value.decode()}")

            results = []
            for i in range(self.num_outputs):
                buf = out_arr[i]
                try:
                    results.append(rt._buffer_to_numpy(buf))
                finally:
                    lib.dl4j_pjrt_buffer_destroy(rt._ctx, buf)
            return results
        finally:
            for h in arg_handles:
                lib.dl4j_pjrt_buffer_destroy(rt._ctx, h)

    def close(self):
        if self._handle:
            self._rt._lib.dl4j_pjrt_exe_destroy(self._rt._ctx, self._handle)
            self._handle = None


class NativeRuntime:
    """PJRT client over a plugin .so (↔ Nd4jBackend + NativeOps init).

    Usage::

        rt = NativeRuntime()                      # the installed libtpu
        exe = rt.compile(stablehlo_text)          # "mlir" format
        outs = exe.execute([np_array, ...])
    """

    def __init__(self, plugin_path: Optional[str] = None,
                 create_options: Optional[dict] = None):
        self._lib = _Lib.get()
        if plugin_path is None:
            plugin_path = default_plugin_path()
        if plugin_path is None:
            raise NativeRuntimeError(
                "no PJRT plugin found: libtpu is not installed")
        if create_options is None:
            create_options = {}  # libtpu needs no PJRT_Client_Create values
        n = len(create_options)
        keys = (ctypes.c_char_p * max(n, 1))()
        types = (ctypes.c_int * max(n, 1))()
        svals = (ctypes.c_char_p * max(n, 1))()
        ivals = (ctypes.c_int64 * max(n, 1))()
        for i, (k, v) in enumerate(create_options.items()):
            keys[i] = k.encode()
            if isinstance(v, str):
                types[i], svals[i] = 0, v.encode()
            elif isinstance(v, (int, bool)):
                types[i], ivals[i] = 1, int(v)
            else:
                raise NativeRuntimeError(
                    f"create option {k}={v!r}: only str/int supported")
        err = _err_buf()
        self._ctx = self._lib.dl4j_pjrt_load(
            plugin_path.encode(), keys, types, svals, ivals, n, err, _ERRLEN)
        if not self._ctx:
            raise NativeRuntimeError(
                f"PJRT client create failed ({plugin_path}): {err.value.decode()}")
        self.plugin_path = plugin_path

    # -- info --------------------------------------------------------------

    def api_version(self):
        major, minor = ctypes.c_int(), ctypes.c_int()
        self._lib.dl4j_pjrt_api_version(self._ctx, ctypes.byref(major),
                                        ctypes.byref(minor))
        return major.value, minor.value

    def platform_name(self) -> str:
        out = _err_buf()
        if self._lib.dl4j_pjrt_platform_name(self._ctx, out, _ERRLEN) != 0:
            raise NativeRuntimeError(out.value.decode())
        return out.value.decode()

    def device_count(self) -> int:
        return self._lib.dl4j_pjrt_device_count(self._ctx)

    def device_description(self, idx: int) -> str:
        out = _err_buf()
        if self._lib.dl4j_pjrt_device_desc(self._ctx, idx, out, _ERRLEN) != 0:
            raise NativeRuntimeError(out.value.decode())
        return out.value.decode()

    # -- compile/execute ---------------------------------------------------

    def compile(self, code, fmt: str = "mlir",
                compile_options: Optional[bytes] = None, *,
                num_replicas: int = 1, num_partitions: int = 1,
                portable: bool = False) -> NativeExecutable:
        """Compile StableHLO MLIR (text or bytecode) or serialized HLO.

        ``num_replicas``/``num_partitions`` build an SPMD executable over
        that many devices; ``portable=True`` leaves the device unassigned so
        ``execute(device=k)`` can target any addressable device."""
        if isinstance(code, str):
            code = code.encode()
        opts = compile_options if compile_options is not None \
            else make_compile_options(num_replicas, num_partitions, portable)
        err = _err_buf()
        h = self._lib.dl4j_pjrt_compile(
            self._ctx, code, len(code), fmt.encode(), opts, len(opts),
            err, _ERRLEN)
        if not h:
            raise NativeRuntimeError(f"compile: {err.value.decode()}")
        return NativeExecutable(self, h, portable=portable)

    def _buffer_to_numpy(self, buf) -> np.ndarray:
        lib = self._lib
        err = _err_buf()
        t = lib.dl4j_pjrt_buffer_type(self._ctx, buf)
        nd = lib.dl4j_pjrt_buffer_ndims(self._ctx, buf)
        dims = (ctypes.c_int64 * max(nd, 1))()
        lib.dl4j_pjrt_buffer_dims(self._ctx, buf, dims, max(nd, 1))
        shape = tuple(dims[i] for i in range(nd))
        size = lib.dl4j_pjrt_buffer_size_bytes(self._ctx, buf, err, _ERRLEN)
        if size < 0:
            raise NativeRuntimeError(f"size query: {err.value.decode()}")
        if t == _BF16:
            dtype, view_as_bf16 = np.dtype(np.uint16), True
        else:
            dtype = _NUMPY_TYPE.get(t)
            view_as_bf16 = False
            if dtype is None:
                raise NativeRuntimeError(f"unsupported output PJRT type {t}")
        out = np.empty(shape, dtype)
        rc = lib.dl4j_pjrt_buffer_to_host(
            self._ctx, buf, out.ctypes.data_as(ctypes.c_void_p),
            int(out.nbytes), err, _ERRLEN)
        if rc != 0:
            raise NativeRuntimeError(f"to_host: {err.value.decode()}")
        if view_as_bf16:
            try:
                import ml_dtypes

                out = out.view(ml_dtypes.bfloat16)
            except ImportError:
                pass  # leave as raw uint16 bits
        return out

    def close(self):
        if getattr(self, "_ctx", None):
            self._lib.dl4j_pjrt_destroy(self._ctx)
            self._ctx = None

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()
