// TPU-native runtime substrate: PJRT C-API binding layer.
//
// ref: libnd4j's NativeOps C ABI + LaunchContext + the JavaCPP JNI surface
// (SURVEY §2.1 rows "C ABI / JNI surface", "Execution/runtime", §2.8 item 1).
// The reference's native runtime owns device discovery, memory movement and
// kernel dispatch behind ~300 exported C functions consumed from the JVM.
// The TPU equivalent is this much smaller surface: PJRT is the device
// runtime (device enumeration, HBM buffers, executable load/run), programs
// are whole compiled XLA modules rather than per-op kernels, and the host
// language binds over a C ABI via ctypes (↔ JavaCPP).
//
// The plugin (.so exporting GetPjrtApi, e.g. libtpu) is dlopen'd at runtime;
// everything else is the stable PJRT C API, so this layer is vendor-neutral.
//
// Build: see native/Makefile (header-only dependency on xla/pjrt/c).

#include <dlfcn.h>

#include <cstdint>
#include <cstring>
#include <string>
#include <vector>

#include "xla/pjrt/c/pjrt_c_api.h"

#define DL4J_EXPORT extern "C" __attribute__((visibility("default")))

namespace {

struct Ctx {
  void* dso = nullptr;
  const PJRT_Api* api = nullptr;
  PJRT_Client* client = nullptr;
  std::vector<PJRT_Device*> devices;  // addressable devices
};

void copy_msg(const char* msg, size_t len, char* err, size_t errlen) {
  if (!err || errlen == 0) return;
  size_t n = len < errlen - 1 ? len : errlen - 1;
  std::memcpy(err, msg, n);
  err[n] = '\0';
}

// Consumes (destroys) the PJRT_Error. Returns true if there was an error.
bool consume_error(const PJRT_Api* api, PJRT_Error* e, char* err, size_t errlen) {
  if (e == nullptr) return false;
  PJRT_Error_Message_Args margs;
  std::memset(&margs, 0, sizeof(margs));
  margs.struct_size = PJRT_Error_Message_Args_STRUCT_SIZE;
  margs.error = e;
  api->PJRT_Error_Message(&margs);
  copy_msg(margs.message, margs.message_size, err, errlen);
  PJRT_Error_Destroy_Args dargs;
  std::memset(&dargs, 0, sizeof(dargs));
  dargs.struct_size = PJRT_Error_Destroy_Args_STRUCT_SIZE;
  dargs.error = e;
  api->PJRT_Error_Destroy(&dargs);
  return true;
}

// Blocks until `event` is ready, then destroys it. Returns false on error.
bool await_event(const PJRT_Api* api, PJRT_Event* event, char* err, size_t errlen) {
  if (event == nullptr) return true;
  PJRT_Event_Await_Args aargs;
  std::memset(&aargs, 0, sizeof(aargs));
  aargs.struct_size = PJRT_Event_Await_Args_STRUCT_SIZE;
  aargs.event = event;
  PJRT_Error* e = api->PJRT_Event_Await(&aargs);
  bool failed = consume_error(api, e, err, errlen);
  PJRT_Event_Destroy_Args dargs;
  std::memset(&dargs, 0, sizeof(dargs));
  dargs.struct_size = PJRT_Event_Destroy_Args_STRUCT_SIZE;
  dargs.event = event;
  api->PJRT_Event_Destroy(&dargs);
  return !failed;
}

}  // namespace

// -- client lifecycle -------------------------------------------------------

// Client create options arrive as parallel arrays: for entry i,
// types[i]==0 means string (str_values[i]), types[i]==1 means int64
// (int_values[i]). Plugins differ in what they require (libtpu: none).
DL4J_EXPORT void* dl4j_pjrt_load(const char* plugin_path, const char** keys,
                                 const int* types, const char** str_values,
                                 const int64_t* int_values, int num_options,
                                 char* err, size_t errlen) {
  void* dso = dlopen(plugin_path, RTLD_NOW | RTLD_LOCAL);
  if (!dso) {
    const char* msg = dlerror();  // clears itself: read exactly once
    if (msg == nullptr) msg = "dlopen failed";
    copy_msg(msg, std::strlen(msg), err, errlen);
    return nullptr;
  }
  using GetApiFn = const PJRT_Api* (*)();
  auto get_api = reinterpret_cast<GetApiFn>(dlsym(dso, "GetPjrtApi"));
  if (!get_api) {
    const char* msg = "plugin has no GetPjrtApi symbol";
    copy_msg(msg, std::strlen(msg), err, errlen);
    dlclose(dso);
    return nullptr;
  }
  const PJRT_Api* api = get_api();

  PJRT_Plugin_Initialize_Args iargs;
  std::memset(&iargs, 0, sizeof(iargs));
  iargs.struct_size = PJRT_Plugin_Initialize_Args_STRUCT_SIZE;
  if (consume_error(api, api->PJRT_Plugin_Initialize(&iargs), err, errlen)) {
    dlclose(dso);
    return nullptr;
  }

  std::vector<PJRT_NamedValue> options(
      static_cast<size_t>(num_options > 0 ? num_options : 0));
  for (int i = 0; i < num_options; ++i) {
    PJRT_NamedValue& nv = options[i];
    std::memset(&nv, 0, sizeof(nv));
    nv.struct_size = PJRT_NamedValue_STRUCT_SIZE;
    nv.name = keys[i];
    nv.name_size = std::strlen(keys[i]);
    if (types[i] == 0) {
      nv.type = PJRT_NamedValue_kString;
      nv.string_value = str_values[i];
      nv.value_size = std::strlen(str_values[i]);
    } else {
      nv.type = PJRT_NamedValue_kInt64;
      nv.int64_value = int_values[i];
      nv.value_size = 1;
    }
  }

  PJRT_Client_Create_Args cargs;
  std::memset(&cargs, 0, sizeof(cargs));
  cargs.struct_size = PJRT_Client_Create_Args_STRUCT_SIZE;
  cargs.create_options = options.empty() ? nullptr : options.data();
  cargs.num_options = options.size();
  if (consume_error(api, api->PJRT_Client_Create(&cargs), err, errlen)) {
    dlclose(dso);
    return nullptr;
  }

  Ctx* ctx = new Ctx();
  ctx->dso = dso;
  ctx->api = api;
  ctx->client = cargs.client;

  PJRT_Client_AddressableDevices_Args dargs;
  std::memset(&dargs, 0, sizeof(dargs));
  dargs.struct_size = PJRT_Client_AddressableDevices_Args_STRUCT_SIZE;
  dargs.client = ctx->client;
  if (consume_error(api, api->PJRT_Client_AddressableDevices(&dargs), err,
                    errlen)) {
    // destroy the client before dropping the ctx — the claim a live client
    // holds on the device must not outlive this failure
    PJRT_Client_Destroy_Args cdargs;
    std::memset(&cdargs, 0, sizeof(cdargs));
    cdargs.struct_size = PJRT_Client_Destroy_Args_STRUCT_SIZE;
    cdargs.client = ctx->client;
    consume_error(api, api->PJRT_Client_Destroy(&cdargs), nullptr, 0);
    delete ctx;
    return nullptr;
  }
  ctx->devices.assign(dargs.addressable_devices,
                      dargs.addressable_devices + dargs.num_addressable_devices);
  return ctx;
}

DL4J_EXPORT void dl4j_pjrt_destroy(void* handle) {
  Ctx* ctx = static_cast<Ctx*>(handle);
  if (!ctx) return;
  if (ctx->client) {
    PJRT_Client_Destroy_Args args;
    std::memset(&args, 0, sizeof(args));
    args.struct_size = PJRT_Client_Destroy_Args_STRUCT_SIZE;
    args.client = ctx->client;
    consume_error(ctx->api, ctx->api->PJRT_Client_Destroy(&args), nullptr, 0);
  }
  // The dso stays loaded: PJRT plugins don't support re-initialization, and
  // unloading while the platform holds global state is UB.
  delete ctx;
}

DL4J_EXPORT int dl4j_pjrt_api_version(void* handle, int* major, int* minor) {
  Ctx* ctx = static_cast<Ctx*>(handle);
  *major = ctx->api->pjrt_api_version.major_version;
  *minor = ctx->api->pjrt_api_version.minor_version;
  return 0;
}

DL4J_EXPORT int dl4j_pjrt_platform_name(void* handle, char* out, size_t outlen) {
  Ctx* ctx = static_cast<Ctx*>(handle);
  PJRT_Client_PlatformName_Args args;
  std::memset(&args, 0, sizeof(args));
  args.struct_size = PJRT_Client_PlatformName_Args_STRUCT_SIZE;
  args.client = ctx->client;
  if (consume_error(ctx->api, ctx->api->PJRT_Client_PlatformName(&args), out,
                    outlen))
    return -1;
  copy_msg(args.platform_name, args.platform_name_size, out, outlen);
  return 0;
}

DL4J_EXPORT int dl4j_pjrt_device_count(void* handle) {
  return static_cast<int>(static_cast<Ctx*>(handle)->devices.size());
}

DL4J_EXPORT int dl4j_pjrt_device_desc(void* handle, int idx, char* out,
                                      size_t outlen) {
  Ctx* ctx = static_cast<Ctx*>(handle);
  if (idx < 0 || idx >= static_cast<int>(ctx->devices.size())) return -1;
  PJRT_Device_GetDescription_Args gargs;
  std::memset(&gargs, 0, sizeof(gargs));
  gargs.struct_size = PJRT_Device_GetDescription_Args_STRUCT_SIZE;
  gargs.device = ctx->devices[idx];
  if (consume_error(ctx->api, ctx->api->PJRT_Device_GetDescription(&gargs), out,
                    outlen))
    return -1;
  PJRT_DeviceDescription_DebugString_Args sargs;
  std::memset(&sargs, 0, sizeof(sargs));
  sargs.struct_size = PJRT_DeviceDescription_DebugString_Args_STRUCT_SIZE;
  sargs.device_description = gargs.device_description;
  if (consume_error(ctx->api,
                    ctx->api->PJRT_DeviceDescription_DebugString(&sargs), out,
                    outlen))
    return -1;
  copy_msg(sargs.debug_string, sargs.debug_string_size, out, outlen);
  return 0;
}

// -- compile ----------------------------------------------------------------

DL4J_EXPORT void* dl4j_pjrt_compile(void* handle, const char* code,
                                    size_t code_size, const char* format,
                                    const char* options, size_t options_size,
                                    char* err, size_t errlen) {
  Ctx* ctx = static_cast<Ctx*>(handle);
  PJRT_Program program;
  std::memset(&program, 0, sizeof(program));
  program.struct_size = PJRT_Program_STRUCT_SIZE;
  program.code = const_cast<char*>(code);
  program.code_size = code_size;
  program.format = format;
  program.format_size = std::strlen(format);

  PJRT_Client_Compile_Args args;
  std::memset(&args, 0, sizeof(args));
  args.struct_size = PJRT_Client_Compile_Args_STRUCT_SIZE;
  args.client = ctx->client;
  args.program = &program;
  args.compile_options = options;
  args.compile_options_size = options_size;
  if (consume_error(ctx->api, ctx->api->PJRT_Client_Compile(&args), err, errlen))
    return nullptr;
  return args.executable;
}

DL4J_EXPORT void dl4j_pjrt_exe_destroy(void* handle, void* exe) {
  Ctx* ctx = static_cast<Ctx*>(handle);
  PJRT_LoadedExecutable_Destroy_Args args;
  std::memset(&args, 0, sizeof(args));
  args.struct_size = PJRT_LoadedExecutable_Destroy_Args_STRUCT_SIZE;
  args.executable = static_cast<PJRT_LoadedExecutable*>(exe);
  consume_error(ctx->api, ctx->api->PJRT_LoadedExecutable_Destroy(&args),
                nullptr, 0);
}

DL4J_EXPORT int dl4j_pjrt_exe_num_outputs(void* handle, void* exe, char* err,
                                          size_t errlen) {
  Ctx* ctx = static_cast<Ctx*>(handle);
  PJRT_LoadedExecutable_GetExecutable_Args gargs;
  std::memset(&gargs, 0, sizeof(gargs));
  gargs.struct_size = PJRT_LoadedExecutable_GetExecutable_Args_STRUCT_SIZE;
  gargs.loaded_executable = static_cast<PJRT_LoadedExecutable*>(exe);
  if (consume_error(ctx->api,
                    ctx->api->PJRT_LoadedExecutable_GetExecutable(&gargs), err,
                    errlen))
    return -1;
  PJRT_Executable_NumOutputs_Args nargs;
  std::memset(&nargs, 0, sizeof(nargs));
  nargs.struct_size = PJRT_Executable_NumOutputs_Args_STRUCT_SIZE;
  nargs.executable = gargs.executable;
  if (consume_error(ctx->api, ctx->api->PJRT_Executable_NumOutputs(&nargs), err,
                    errlen))
    return -1;
  return static_cast<int>(nargs.num_outputs);
}

// -- buffers ----------------------------------------------------------------

DL4J_EXPORT void* dl4j_pjrt_buffer_from_host(void* handle, const void* data,
                                             int type, const int64_t* dims,
                                             int ndims, int device_index,
                                             char* err, size_t errlen) {
  Ctx* ctx = static_cast<Ctx*>(handle);
  if (device_index < 0 || device_index >= static_cast<int>(ctx->devices.size())) {
    const char* msg = "bad device index";
    copy_msg(msg, std::strlen(msg), err, errlen);
    return nullptr;
  }
  PJRT_Client_BufferFromHostBuffer_Args args;
  std::memset(&args, 0, sizeof(args));
  args.struct_size = PJRT_Client_BufferFromHostBuffer_Args_STRUCT_SIZE;
  args.client = ctx->client;
  args.data = data;
  args.type = static_cast<PJRT_Buffer_Type>(type);
  args.dims = dims;
  args.num_dims = static_cast<size_t>(ndims);
  args.host_buffer_semantics =
      PJRT_HostBufferSemantics_kImmutableUntilTransferCompletes;
  args.device = ctx->devices[device_index];
  if (consume_error(ctx->api, ctx->api->PJRT_Client_BufferFromHostBuffer(&args),
                    err, errlen))
    return nullptr;
  if (!await_event(ctx->api, args.done_with_host_buffer, err, errlen)) {
    // don't leak the device buffer when the H2D transfer failed
    PJRT_Buffer_Destroy_Args dargs;
    std::memset(&dargs, 0, sizeof(dargs));
    dargs.struct_size = PJRT_Buffer_Destroy_Args_STRUCT_SIZE;
    dargs.buffer = args.buffer;
    consume_error(ctx->api, ctx->api->PJRT_Buffer_Destroy(&dargs), nullptr, 0);
    return nullptr;
  }
  return args.buffer;
}

DL4J_EXPORT void dl4j_pjrt_buffer_destroy(void* handle, void* buf) {
  Ctx* ctx = static_cast<Ctx*>(handle);
  PJRT_Buffer_Destroy_Args args;
  std::memset(&args, 0, sizeof(args));
  args.struct_size = PJRT_Buffer_Destroy_Args_STRUCT_SIZE;
  args.buffer = static_cast<PJRT_Buffer*>(buf);
  consume_error(ctx->api, ctx->api->PJRT_Buffer_Destroy(&args), nullptr, 0);
}

DL4J_EXPORT int dl4j_pjrt_buffer_type(void* handle, void* buf) {
  Ctx* ctx = static_cast<Ctx*>(handle);
  PJRT_Buffer_ElementType_Args args;
  std::memset(&args, 0, sizeof(args));
  args.struct_size = PJRT_Buffer_ElementType_Args_STRUCT_SIZE;
  args.buffer = static_cast<PJRT_Buffer*>(buf);
  if (consume_error(ctx->api, ctx->api->PJRT_Buffer_ElementType(&args), nullptr,
                    0))
    return -1;
  return static_cast<int>(args.type);
}

DL4J_EXPORT int dl4j_pjrt_buffer_ndims(void* handle, void* buf) {
  Ctx* ctx = static_cast<Ctx*>(handle);
  PJRT_Buffer_Dimensions_Args args;
  std::memset(&args, 0, sizeof(args));
  args.struct_size = PJRT_Buffer_Dimensions_Args_STRUCT_SIZE;
  args.buffer = static_cast<PJRT_Buffer*>(buf);
  if (consume_error(ctx->api, ctx->api->PJRT_Buffer_Dimensions(&args), nullptr,
                    0))
    return -1;
  return static_cast<int>(args.num_dims);
}

DL4J_EXPORT int dl4j_pjrt_buffer_dims(void* handle, void* buf, int64_t* out,
                                      int cap) {
  Ctx* ctx = static_cast<Ctx*>(handle);
  PJRT_Buffer_Dimensions_Args args;
  std::memset(&args, 0, sizeof(args));
  args.struct_size = PJRT_Buffer_Dimensions_Args_STRUCT_SIZE;
  args.buffer = static_cast<PJRT_Buffer*>(buf);
  if (consume_error(ctx->api, ctx->api->PJRT_Buffer_Dimensions(&args), nullptr,
                    0))
    return -1;
  int n = static_cast<int>(args.num_dims);
  for (int i = 0; i < n && i < cap; ++i) out[i] = args.dims[i];
  return n;
}

DL4J_EXPORT long long dl4j_pjrt_buffer_size_bytes(void* handle, void* buf,
                                                  char* err, size_t errlen) {
  Ctx* ctx = static_cast<Ctx*>(handle);
  PJRT_Buffer_ToHostBuffer_Args args;
  std::memset(&args, 0, sizeof(args));
  args.struct_size = PJRT_Buffer_ToHostBuffer_Args_STRUCT_SIZE;
  args.src = static_cast<PJRT_Buffer*>(buf);
  args.dst = nullptr;  // size query
  if (consume_error(ctx->api, ctx->api->PJRT_Buffer_ToHostBuffer(&args), err,
                    errlen))
    return -1;
  return static_cast<long long>(args.dst_size);
}

DL4J_EXPORT int dl4j_pjrt_buffer_to_host(void* handle, void* buf, void* dst,
                                         long long dst_size, char* err,
                                         size_t errlen) {
  Ctx* ctx = static_cast<Ctx*>(handle);
  PJRT_Buffer_ToHostBuffer_Args args;
  std::memset(&args, 0, sizeof(args));
  args.struct_size = PJRT_Buffer_ToHostBuffer_Args_STRUCT_SIZE;
  args.src = static_cast<PJRT_Buffer*>(buf);
  args.dst = dst;
  args.dst_size = static_cast<size_t>(dst_size);
  if (consume_error(ctx->api, ctx->api->PJRT_Buffer_ToHostBuffer(&args), err,
                    errlen))
    return -1;
  if (!await_event(ctx->api, args.event, err, errlen)) return -1;
  return 0;
}

// -- execute ----------------------------------------------------------------

// Single-device synchronous execute: device buffers in, device buffers out.
// out_buffers must have capacity for num_outputs entries.
// device_index >= 0 selects the execution device for PORTABLE executables
// (compiled with compile_portable_executable; PJRT requires execute_device
// for those); pass -1 for executables with a built-in device assignment.
DL4J_EXPORT int dl4j_pjrt_execute(void* handle, void* exe, void** arg_buffers,
                                  int num_args, void** out_buffers,
                                  int num_outputs, int device_index, char* err,
                                  size_t errlen) {
  Ctx* ctx = static_cast<Ctx*>(handle);
  if (device_index >= static_cast<int>(ctx->devices.size())) {
    const char* msg = "bad execute device index";
    copy_msg(msg, std::strlen(msg), err, errlen);
    return -1;
  }

  PJRT_ExecuteOptions options;
  std::memset(&options, 0, sizeof(options));
  options.struct_size = PJRT_ExecuteOptions_STRUCT_SIZE;

  std::vector<PJRT_Buffer*> args_vec(num_args);
  for (int i = 0; i < num_args; ++i)
    args_vec[i] = static_cast<PJRT_Buffer*>(arg_buffers[i]);
  PJRT_Buffer* const* arg_list = args_vec.data();

  std::vector<PJRT_Buffer*> outs_vec(num_outputs, nullptr);
  PJRT_Buffer** out_list = outs_vec.data();

  PJRT_Event* device_complete = nullptr;

  PJRT_LoadedExecutable_Execute_Args eargs;
  std::memset(&eargs, 0, sizeof(eargs));
  eargs.struct_size = PJRT_LoadedExecutable_Execute_Args_STRUCT_SIZE;
  eargs.executable = static_cast<PJRT_LoadedExecutable*>(exe);
  eargs.options = &options;
  eargs.argument_lists = &arg_list;
  eargs.num_devices = 1;
  eargs.num_args = static_cast<size_t>(num_args);
  eargs.output_lists = &out_list;
  eargs.device_complete_events = &device_complete;
  if (device_index >= 0) eargs.execute_device = ctx->devices[device_index];
  if (consume_error(ctx->api, ctx->api->PJRT_LoadedExecutable_Execute(&eargs),
                    err, errlen))
    return -1;
  if (!await_event(ctx->api, device_complete, err, errlen)) {
    // execution failed after output buffers were allocated: free them here
    // (the caller never sees them)
    for (PJRT_Buffer* b : outs_vec) {
      if (b == nullptr) continue;
      PJRT_Buffer_Destroy_Args dargs;
      std::memset(&dargs, 0, sizeof(dargs));
      dargs.struct_size = PJRT_Buffer_Destroy_Args_STRUCT_SIZE;
      dargs.buffer = b;
      consume_error(ctx->api, ctx->api->PJRT_Buffer_Destroy(&dargs), nullptr, 0);
    }
    return -1;
  }
  for (int i = 0; i < num_outputs; ++i) out_buffers[i] = outs_vec[i];
  return 0;
}
