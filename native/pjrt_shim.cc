// PJRT C API client shim: the framework's native runtime binding.
//
// Role (SURVEY.md §2.11): the reference's native tier is the ND4J C++
// backend loaded over JavaCPP (external nd4j-native / nd4j-cuda modules,
// reference pom.xml:125-160).  The TPU-native equivalent binds the PJRT
// C API: dlopen a PJRT plugin (libtpu or a CPU plugin), create a
// client, enumerate devices, and compile + execute StableHLO programs —
// C++ talking to the accelerator with no Python in the path.
//
// Exposed as a small C ABI consumed from Python via ctypes (pybind11 is
// not in the image).  All PJRT structs are zero-initialised and sized
// with the *_STRUCT_SIZE traits so the shim stays forward-compatible
// with plugins implementing newer minor versions of the API.

#include <dlfcn.h>
#include <string.h>

#include <cstdint>
#include <cstdio>
#include <mutex>
#include <string>
#include <unordered_map>
#include <vector>

#include "xla/pjrt/c/pjrt_c_api.h"

namespace {

// One compiled program held by the executable cache: the loaded
// executable plus its (eagerly captured) output signature, so repeat
// executions skip every introspection call.  This is the
// CudnnConvolutionHelper descriptor/algo-cache role
// (reference CudnnConvolutionHelper.java:64-140) rebased onto PJRT:
// compile once per distinct (program, shapes, dtypes), then the hot
// path is transfer + execute only.
struct ExecEntry {
  PJRT_LoadedExecutable* loaded = nullptr;
  PJRT_Executable* exec = nullptr;  // owned; destroy with entry
  size_t num_outputs = 0;
  std::vector<PJRT_Buffer_Type> out_types;
  std::vector<std::vector<int64_t>> out_dims;
  // in-flight executions pin the entry; cache_clear defers the destroy
  // of pinned entries until the last execution unpins
  int pins = 0;
  bool dead = false;
  // full cache key (program text ‖ '\0' ‖ compile options), compared on
  // every hash hit: a 64-bit hash collision must miss, never silently
  // execute the wrong program
  std::string key_text;
};

struct DeviceBuf {
  PJRT_Buffer* buf = nullptr;
  int pins = 0;   // in-flight executions referencing this buffer
  bool dead = false;  // freed while pinned: destroy on last unpin
};

struct ShimClient {
  void* dl_handle = nullptr;
  const PJRT_Api* api = nullptr;
  PJRT_Client* client = nullptr;
  // Executable cache: FNV-1a hash of (program text ‖ compile options) →
  // bucket of exec ids whose stored key_text is compared on lookup
  // (hash collisions become misses, not wrong-program executions).
  // Input/output shapes and dtypes are part of the StableHLO program
  // text (static shapes), so the key subsumes (shapes, dtype).
  std::mutex mu;
  std::unordered_map<uint64_t, std::vector<int64_t>> cache;
  std::unordered_map<int64_t, ExecEntry> execs;
  int64_t next_exec_id = 0;
  int64_t hits = 0;
  int64_t misses = 0;
  // Persistent device buffers (the ND4J device-resident INDArray role):
  // model parameters upload once and are referenced by id in execute
  // calls, so the hot path transfers activations only.
  std::unordered_map<int64_t, DeviceBuf> buffers;
  int64_t next_buffer_id = 1;
};

void destroy_exec_entry(const PJRT_Api* api, ExecEntry& e) {
  if (e.exec != nullptr) {
    PJRT_Executable_Destroy_Args d;
    memset(&d, 0, sizeof(d));
    d.struct_size = PJRT_Executable_Destroy_Args_STRUCT_SIZE;
    d.executable = e.exec;
    PJRT_Error* err = api->PJRT_Executable_Destroy(&d);
    if (err != nullptr) {
      PJRT_Error_Destroy_Args de;
      memset(&de, 0, sizeof(de));
      de.struct_size = PJRT_Error_Destroy_Args_STRUCT_SIZE;
      de.error = err;
      api->PJRT_Error_Destroy(&de);
    }
    e.exec = nullptr;
  }
  if (e.loaded != nullptr) {
    PJRT_LoadedExecutable_Destroy_Args d;
    memset(&d, 0, sizeof(d));
    d.struct_size = PJRT_LoadedExecutable_Destroy_Args_STRUCT_SIZE;
    d.executable = e.loaded;
    PJRT_Error* err = api->PJRT_LoadedExecutable_Destroy(&d);
    if (err != nullptr) {
      PJRT_Error_Destroy_Args de;
      memset(&de, 0, sizeof(de));
      de.struct_size = PJRT_Error_Destroy_Args_STRUCT_SIZE;
      de.error = err;
      api->PJRT_Error_Destroy(&de);
    }
    e.loaded = nullptr;
  }
}

void destroy_pjrt_buffer(const PJRT_Api* api, PJRT_Buffer* buf) {
  PJRT_Buffer_Destroy_Args d;
  memset(&d, 0, sizeof(d));
  d.struct_size = PJRT_Buffer_Destroy_Args_STRUCT_SIZE;
  d.buffer = buf;
  PJRT_Error* err = api->PJRT_Buffer_Destroy(&d);
  if (err != nullptr) {
    PJRT_Error_Destroy_Args de;
    memset(&de, 0, sizeof(de));
    de.struct_size = PJRT_Error_Destroy_Args_STRUCT_SIZE;
    de.error = err;
    api->PJRT_Error_Destroy(&de);
  }
}

uint64_t fnv1a(const char* data, size_t n, uint64_t h = 1469598103934665603ULL) {
  for (size_t i = 0; i < n; ++i) {
    h ^= (unsigned char)data[i];
    h *= 1099511628211ULL;
  }
  return h;
}

// Copy a PJRT_Error message into err_buf and destroy the error.
void consume_error(const PJRT_Api* api, PJRT_Error* error, char* err_buf,
                   int err_len) {
  if (error == nullptr) return;
  PJRT_Error_Message_Args msg_args;
  memset(&msg_args, 0, sizeof(msg_args));
  msg_args.struct_size = PJRT_Error_Message_Args_STRUCT_SIZE;
  msg_args.error = error;
  api->PJRT_Error_Message(&msg_args);
  if (err_buf != nullptr && err_len > 0) {
    size_t n = msg_args.message_size < (size_t)(err_len - 1)
                   ? msg_args.message_size
                   : (size_t)(err_len - 1);
    memcpy(err_buf, msg_args.message, n);
    err_buf[n] = '\0';
  }
  PJRT_Error_Destroy_Args destroy_args;
  memset(&destroy_args, 0, sizeof(destroy_args));
  destroy_args.struct_size = PJRT_Error_Destroy_Args_STRUCT_SIZE;
  destroy_args.error = error;
  api->PJRT_Error_Destroy(&destroy_args);
}

void set_err(char* err_buf, int err_len, const char* msg) {
  if (err_buf != nullptr && err_len > 0) {
    snprintf(err_buf, err_len, "%s", msg);
  }
}

bool await_event(const PJRT_Api* api, PJRT_Event* event, char* err_buf,
                 int err_len) {
  if (event == nullptr) return true;
  PJRT_Event_Await_Args await_args;
  memset(&await_args, 0, sizeof(await_args));
  await_args.struct_size = PJRT_Event_Await_Args_STRUCT_SIZE;
  await_args.event = event;
  PJRT_Error* error = api->PJRT_Event_Await(&await_args);
  bool ok = error == nullptr;
  if (!ok) consume_error(api, error, err_buf, err_len);
  PJRT_Event_Destroy_Args destroy_args;
  memset(&destroy_args, 0, sizeof(destroy_args));
  destroy_args.struct_size = PJRT_Event_Destroy_Args_STRUCT_SIZE;
  destroy_args.event = event;
  api->PJRT_Event_Destroy(&destroy_args);
  return ok;
}

std::vector<PJRT_Device*> addressable_devices(ShimClient* shim) {
  PJRT_Client_AddressableDevices_Args args;
  memset(&args, 0, sizeof(args));
  args.struct_size = PJRT_Client_AddressableDevices_Args_STRUCT_SIZE;
  args.client = shim->client;
  PJRT_Error* error =
      shim->api->PJRT_Client_AddressableDevices(&args);
  if (error != nullptr) {
    consume_error(shim->api, error, nullptr, 0);
    return {};
  }
  return std::vector<PJRT_Device*>(
      args.addressable_devices,
      args.addressable_devices + args.num_addressable_devices);
}

}  // namespace

extern "C" {

// Load a PJRT plugin and create a client with named creation options
// (the PJRT_NamedValue list some plugins require, e.g. a topology).
// keys[i] pairs with str_vals[i] when is_int[i] == 0, else with
// int_vals[i].  Returns an opaque handle or
// nullptr (with err_buf filled).
void* dl4j_pjrt_client_create_opts(const char* plugin_path,
                                   const char* const* keys,
                                   const char* const* str_vals,
                                   const int64_t* int_vals,
                                   const int* is_int, int n_opts,
                                   char* err_buf, int err_len) {
  void* dl = dlopen(plugin_path, RTLD_NOW | RTLD_LOCAL);
  if (dl == nullptr) {
    set_err(err_buf, err_len, dlerror());
    return nullptr;
  }
  using GetPjrtApiFn = const PJRT_Api* (*)();
  auto get_api =
      reinterpret_cast<GetPjrtApiFn>(dlsym(dl, "GetPjrtApi"));
  if (get_api == nullptr) {
    set_err(err_buf, err_len, "plugin has no GetPjrtApi symbol");
    dlclose(dl);
    return nullptr;
  }
  const PJRT_Api* api = get_api();
  if (api == nullptr) {
    set_err(err_buf, err_len, "GetPjrtApi returned null");
    dlclose(dl);
    return nullptr;
  }

  // The C API contract: initialize the plugin before any other call.
  // libtpu aborts the process inside PJRT_Client_Create without it.
  PJRT_Plugin_Initialize_Args init_args;
  memset(&init_args, 0, sizeof(init_args));
  init_args.struct_size = PJRT_Plugin_Initialize_Args_STRUCT_SIZE;
  PJRT_Error* init_error = api->PJRT_Plugin_Initialize(&init_args);
  if (init_error != nullptr) {
    consume_error(api, init_error, err_buf, err_len);
    dlclose(dl);
    return nullptr;
  }

  std::vector<PJRT_NamedValue> options((size_t)n_opts);
  for (int i = 0; i < n_opts; ++i) {
    PJRT_NamedValue* nv = &options[i];
    memset(nv, 0, sizeof(*nv));
    nv->struct_size = PJRT_NamedValue_STRUCT_SIZE;
    nv->name = keys[i];
    nv->name_size = strlen(keys[i]);
    if (is_int[i]) {
      nv->type = PJRT_NamedValue_kInt64;
      nv->int64_value = int_vals[i];
      nv->value_size = 1;
    } else {
      nv->type = PJRT_NamedValue_kString;
      nv->string_value = str_vals[i];
      nv->value_size = strlen(str_vals[i]);
    }
  }

  PJRT_Client_Create_Args args;
  memset(&args, 0, sizeof(args));
  args.struct_size = PJRT_Client_Create_Args_STRUCT_SIZE;
  args.create_options = options.data();
  args.num_options = (size_t)n_opts;
  PJRT_Error* error = api->PJRT_Client_Create(&args);
  if (error != nullptr) {
    consume_error(api, error, err_buf, err_len);
    dlclose(dl);
    return nullptr;
  }
  ShimClient* shim = new ShimClient();
  shim->dl_handle = dl;
  shim->api = api;
  shim->client = args.client;
  return shim;
}

// Optionless create (CPU-style plugins).
void* dl4j_pjrt_client_create(const char* plugin_path, char* err_buf,
                              int err_len) {
  return dl4j_pjrt_client_create_opts(plugin_path, nullptr, nullptr,
                                      nullptr, nullptr, 0, err_buf,
                                      err_len);
}

void dl4j_pjrt_client_destroy(void* handle) {
  if (handle == nullptr) return;
  ShimClient* shim = static_cast<ShimClient*>(handle);
  for (auto& kv : shim->execs) {
    destroy_exec_entry(shim->api, kv.second);
  }
  shim->execs.clear();
  shim->cache.clear();
  for (auto& kv : shim->buffers) {
    destroy_pjrt_buffer(shim->api, kv.second.buf);
  }
  shim->buffers.clear();
  if (shim->client != nullptr) {
    PJRT_Client_Destroy_Args args;
    memset(&args, 0, sizeof(args));
    args.struct_size = PJRT_Client_Destroy_Args_STRUCT_SIZE;
    args.client = shim->client;
    consume_error(shim->api, shim->api->PJRT_Client_Destroy(&args),
                  nullptr, 0);
  }
  // NOTE: the plugin .so stays mapped (plugins generally do not support
  // re-initialisation after dlclose).
  delete shim;
}

int dl4j_pjrt_api_version(void* handle, int* major, int* minor) {
  ShimClient* shim = static_cast<ShimClient*>(handle);
  if (shim == nullptr || shim->api == nullptr) return -1;
  *major = shim->api->pjrt_api_version.major_version;
  *minor = shim->api->pjrt_api_version.minor_version;
  return 0;
}

int dl4j_pjrt_platform_name(void* handle, char* out, int out_len) {
  ShimClient* shim = static_cast<ShimClient*>(handle);
  PJRT_Client_PlatformName_Args args;
  memset(&args, 0, sizeof(args));
  args.struct_size = PJRT_Client_PlatformName_Args_STRUCT_SIZE;
  args.client = shim->client;
  PJRT_Error* error = shim->api->PJRT_Client_PlatformName(&args);
  if (error != nullptr) {
    consume_error(shim->api, error, out, out_len);
    return -1;
  }
  size_t n = args.platform_name_size < (size_t)(out_len - 1)
                 ? args.platform_name_size
                 : (size_t)(out_len - 1);
  memcpy(out, args.platform_name, n);
  out[n] = '\0';
  return (int)n;
}

int dl4j_pjrt_device_count(void* handle) {
  ShimClient* shim = static_cast<ShimClient*>(handle);
  return (int)addressable_devices(shim).size();
}

// ---------------------------------------------------------------------------
// Executable cache + typed multi-output execution (the production API).
// ---------------------------------------------------------------------------

// Compile a textual StableHLO/MLIR module (or return the cached
// executable).  The cache key is the FNV-1a hash of the program text and
// the serialized compile options; StableHLO embeds every operand/result
// shape and dtype, so distinct shapes/dtypes hash to distinct programs.
// Returns an executable id >= 0, or -1 (err_buf filled).  `was_hit`
// (optional) is set to 1 on a cache hit.
int64_t dl4j_pjrt_compile_cached(void* handle, const char* mlir_code,
                                 const char* compile_options,
                                 int64_t compile_options_size,
                                 int* was_hit, char* err_buf,
                                 int err_len) {
  ShimClient* shim = static_cast<ShimClient*>(handle);
  const PJRT_Api* api = shim->api;
  if (was_hit != nullptr) *was_hit = 0;

  size_t code_size = strlen(mlir_code);
  uint64_t key = fnv1a(mlir_code, code_size);
  if (compile_options != nullptr && compile_options_size > 0) {
    key = fnv1a(compile_options, (size_t)compile_options_size, key);
  }
  // the full key, stored per entry and compared on every hash hit
  std::string key_text(mlir_code, code_size);
  key_text.push_back('\0');
  if (compile_options != nullptr && compile_options_size > 0) {
    key_text.append(compile_options, (size_t)compile_options_size);
  }
  // caller must hold shim->mu
  auto find_verified = [shim, key, &key_text]() -> int64_t {
    auto it = shim->cache.find(key);
    if (it == shim->cache.end()) return -1;
    for (int64_t id : it->second) {
      auto eit = shim->execs.find(id);
      if (eit != shim->execs.end() && !eit->second.dead &&
          eit->second.key_text == key_text) {
        return id;
      }
    }
    return -1;
  };
  {
    std::lock_guard<std::mutex> lock(shim->mu);
    int64_t id = find_verified();
    if (id >= 0) {
      ++shim->hits;
      if (was_hit != nullptr) *was_hit = 1;
      return id;
    }
  }

  // -- compile (outside the lock: plugins may compile for seconds) --------
  PJRT_Program program;
  memset(&program, 0, sizeof(program));
  program.struct_size = PJRT_Program_STRUCT_SIZE;
  program.code = const_cast<char*>(mlir_code);
  program.code_size = code_size;
  static const char kFormat[] = "mlir";
  program.format = kFormat;
  program.format_size = sizeof(kFormat) - 1;

  PJRT_Client_Compile_Args compile_args;
  memset(&compile_args, 0, sizeof(compile_args));
  compile_args.struct_size = PJRT_Client_Compile_Args_STRUCT_SIZE;
  compile_args.client = shim->client;
  compile_args.program = &program;
  compile_args.compile_options =
      compile_options != nullptr ? compile_options : "";
  compile_args.compile_options_size = (size_t)compile_options_size;
  PJRT_Error* error = api->PJRT_Client_Compile(&compile_args);
  if (error != nullptr) {
    consume_error(api, error, err_buf, err_len);
    return -1;
  }

  ExecEntry entry;
  entry.loaded = compile_args.executable;

  // -- capture the output signature once ----------------------------------
  // (on any introspection error, destroy the freshly compiled executable
  // before returning — no retry may leak device memory)
  PJRT_LoadedExecutable_GetExecutable_Args get_args;
  memset(&get_args, 0, sizeof(get_args));
  get_args.struct_size = PJRT_LoadedExecutable_GetExecutable_Args_STRUCT_SIZE;
  get_args.loaded_executable = entry.loaded;
  error = api->PJRT_LoadedExecutable_GetExecutable(&get_args);
  if (error != nullptr) {
    consume_error(api, error, err_buf, err_len);
    destroy_exec_entry(api, entry);
    return -1;
  }
  entry.exec = get_args.executable;

  PJRT_Executable_NumOutputs_Args num_args;
  memset(&num_args, 0, sizeof(num_args));
  num_args.struct_size = PJRT_Executable_NumOutputs_Args_STRUCT_SIZE;
  num_args.executable = entry.exec;
  error = api->PJRT_Executable_NumOutputs(&num_args);
  if (error != nullptr) {
    consume_error(api, error, err_buf, err_len);
    destroy_exec_entry(api, entry);
    return -1;
  }
  entry.num_outputs = num_args.num_outputs;

  PJRT_Executable_OutputElementTypes_Args type_args;
  memset(&type_args, 0, sizeof(type_args));
  type_args.struct_size = PJRT_Executable_OutputElementTypes_Args_STRUCT_SIZE;
  type_args.executable = entry.exec;
  error = api->PJRT_Executable_OutputElementTypes(&type_args);
  if (error != nullptr) {
    consume_error(api, error, err_buf, err_len);
    destroy_exec_entry(api, entry);
    return -1;
  }
  entry.out_types.assign(type_args.output_types,
                         type_args.output_types + type_args.num_output_types);

  PJRT_Executable_OutputDimensions_Args dim_args;
  memset(&dim_args, 0, sizeof(dim_args));
  dim_args.struct_size = PJRT_Executable_OutputDimensions_Args_STRUCT_SIZE;
  dim_args.executable = entry.exec;
  error = api->PJRT_Executable_OutputDimensions(&dim_args);
  if (error != nullptr) {
    consume_error(api, error, err_buf, err_len);
    destroy_exec_entry(api, entry);
    return -1;
  }
  const int64_t* dp = dim_args.dims;
  for (size_t i = 0; i < dim_args.num_outputs; ++i) {
    entry.out_dims.emplace_back(dp, dp + dim_args.dim_sizes[i]);
    dp += dim_args.dim_sizes[i];
  }

  std::lock_guard<std::mutex> lock(shim->mu);
  int64_t existing = find_verified();
  if (existing >= 0) {
    // Lost a compile race; keep the first entry, destroy our duplicate.
    destroy_exec_entry(api, entry);
    ++shim->hits;
    if (was_hit != nullptr) *was_hit = 1;
    return existing;
  }
  ++shim->misses;
  int64_t id = shim->next_exec_id++;
  entry.key_text = std::move(key_text);
  shim->execs.emplace(id, entry);
  shim->cache[key].push_back(id);
  return id;
}

// Drop every cached executable (bounded-memory control for long-lived
// clients serving many program shapes; the cuDNN-cache analogue is
// per-layer bounded — here the caller owns the policy).  Entries pinned
// by in-flight executions are destroyed when they unpin.  Returns the
// number of entries scheduled for destruction.
int64_t dl4j_pjrt_cache_clear(void* handle) {
  ShimClient* shim = static_cast<ShimClient*>(handle);
  std::lock_guard<std::mutex> lock(shim->mu);
  int64_t n = 0;
  for (auto it = shim->execs.begin(); it != shim->execs.end();) {
    ++n;
    if (it->second.pins == 0) {
      destroy_exec_entry(shim->api, it->second);
      it = shim->execs.erase(it);
    } else {
      it->second.dead = true;
      ++it;
    }
  }
  shim->cache.clear();
  return n;
}

// Evict one cached executable by id (the LRU policy lives in the
// Python caller — `NativeModelRunner` — the shim only provides
// per-entry destruction).  The id is unlinked from its hash bucket so
// lookups can never return it again; an entry pinned by an in-flight
// execution is marked dead and destroyed on its last unpin.  Returns 1
// if the id was found and evicted, 0 if unknown or already dead.
int64_t dl4j_pjrt_cache_evict(void* handle, int64_t exec_id) {
  ShimClient* shim = static_cast<ShimClient*>(handle);
  std::lock_guard<std::mutex> lock(shim->mu);
  auto it = shim->execs.find(exec_id);
  if (it == shim->execs.end() || it->second.dead) return 0;
  // recompute the bucket hash from the stored key_text
  // (program text ‖ '\0' ‖ compile options — the same recipe
  // dl4j_pjrt_compile_cached hashes with)
  const std::string& kt = it->second.key_text;
  size_t p = kt.find('\0');
  if (p == std::string::npos) p = kt.size();
  uint64_t key = fnv1a(kt.data(), p);
  if (kt.size() > p + 1) {
    key = fnv1a(kt.data() + p + 1, kt.size() - p - 1, key);
  }
  auto bit = shim->cache.find(key);
  if (bit != shim->cache.end()) {
    std::vector<int64_t>& ids = bit->second;
    for (size_t i = 0; i < ids.size();) {
      if (ids[i] == exec_id) {
        ids.erase(ids.begin() + (ptrdiff_t)i);
      } else {
        ++i;
      }
    }
    if (ids.empty()) shim->cache.erase(bit);
  }
  if (it->second.pins == 0) {
    destroy_exec_entry(shim->api, it->second);
    shim->execs.erase(it);
  } else {
    it->second.dead = true;
  }
  return 1;
}

int dl4j_pjrt_cache_stats(void* handle, int64_t* hits, int64_t* misses,
                          int64_t* entries) {
  ShimClient* shim = static_cast<ShimClient*>(handle);
  if (shim == nullptr) return -1;
  std::lock_guard<std::mutex> lock(shim->mu);
  if (hits != nullptr) *hits = shim->hits;
  if (misses != nullptr) *misses = shim->misses;
  if (entries != nullptr) *entries = (int64_t)shim->cache.size();
  return 0;
}

int dl4j_pjrt_exec_num_outputs(void* handle, int64_t exec_id) {
  ShimClient* shim = static_cast<ShimClient*>(handle);
  std::lock_guard<std::mutex> lock(shim->mu);
  auto it = shim->execs.find(exec_id);
  if (it == shim->execs.end() || it->second.dead) return -1;
  return (int)it->second.num_outputs;
}

// Per-output dtype codes (PJRT_Buffer_Type values), ranks, and dims
// (all outputs' dims concatenated).  Returns num_outputs, or -1 if the
// provided arrays are too small / exec_id is invalid.
int dl4j_pjrt_exec_output_info(void* handle, int64_t exec_id, int* dtypes,
                               int* ranks, int64_t* dims, int max_outputs,
                               int max_total_dims) {
  ShimClient* shim = static_cast<ShimClient*>(handle);
  std::lock_guard<std::mutex> lock(shim->mu);
  auto eit = shim->execs.find(exec_id);
  if (eit == shim->execs.end() || eit->second.dead) return -1;
  const ExecEntry& e = eit->second;
  if ((int)e.num_outputs > max_outputs) return -1;
  int total = 0;
  for (size_t i = 0; i < e.num_outputs; ++i) {
    dtypes[i] = (int)e.out_types[i];
    ranks[i] = (int)e.out_dims[i].size();
    total += ranks[i];
  }
  if (total > max_total_dims) return -1;
  int64_t* dp = dims;
  for (size_t i = 0; i < e.num_outputs; ++i) {
    for (int64_t d : e.out_dims[i]) *dp++ = d;
  }
  return (int)e.num_outputs;
}

// The PJRT_Buffer_Type code for a dtype name ("f32", "bf16", "s32",
// "pred", ...) so callers never hardcode enum values.  -1 if unknown.
int dl4j_pjrt_dtype_code(const char* name) {
  std::string s(name == nullptr ? "" : name);
  if (s == "pred" || s == "bool") return (int)PJRT_Buffer_Type_PRED;
  if (s == "s8" || s == "int8") return (int)PJRT_Buffer_Type_S8;
  if (s == "s16" || s == "int16") return (int)PJRT_Buffer_Type_S16;
  if (s == "s32" || s == "int32") return (int)PJRT_Buffer_Type_S32;
  if (s == "s64" || s == "int64") return (int)PJRT_Buffer_Type_S64;
  if (s == "u8" || s == "uint8") return (int)PJRT_Buffer_Type_U8;
  if (s == "u16" || s == "uint16") return (int)PJRT_Buffer_Type_U16;
  if (s == "u32" || s == "uint32") return (int)PJRT_Buffer_Type_U32;
  if (s == "u64" || s == "uint64") return (int)PJRT_Buffer_Type_U64;
  if (s == "f16" || s == "float16") return (int)PJRT_Buffer_Type_F16;
  if (s == "f32" || s == "float32") return (int)PJRT_Buffer_Type_F32;
  if (s == "f64" || s == "float64") return (int)PJRT_Buffer_Type_F64;
  if (s == "bf16" || s == "bfloat16") return (int)PJRT_Buffer_Type_BF16;
  return -1;
}

// Upload a typed host array into a persistent device buffer; returns a
// buffer id (>= 1) usable as an execute input, or -1.  This is how model
// parameters stay device-resident across calls (the ND4J INDArray role):
// the hot path then transfers activations only.
int64_t dl4j_pjrt_buffer_from_host(void* handle, const void* data,
                                   int dtype, const int64_t* dims,
                                   int rank, char* err_buf, int err_len) {
  ShimClient* shim = static_cast<ShimClient*>(handle);
  const PJRT_Api* api = shim->api;
  std::vector<PJRT_Device*> devices = addressable_devices(shim);
  if (devices.empty()) {
    set_err(err_buf, err_len, "no addressable devices");
    return -1;
  }
  PJRT_Client_BufferFromHostBuffer_Args h2d;
  memset(&h2d, 0, sizeof(h2d));
  h2d.struct_size = PJRT_Client_BufferFromHostBuffer_Args_STRUCT_SIZE;
  h2d.client = shim->client;
  h2d.data = data;
  h2d.type = (PJRT_Buffer_Type)dtype;
  h2d.dims = dims;
  h2d.num_dims = (size_t)rank;
  h2d.host_buffer_semantics =
      PJRT_HostBufferSemantics_kImmutableUntilTransferCompletes;
  h2d.device = devices[0];
  PJRT_Error* error = api->PJRT_Client_BufferFromHostBuffer(&h2d);
  if (error != nullptr) {
    consume_error(api, error, err_buf, err_len);
    return -1;
  }
  if (!await_event(api, h2d.done_with_host_buffer, err_buf, err_len)) {
    PJRT_Buffer_Destroy_Args d;
    memset(&d, 0, sizeof(d));
    d.struct_size = PJRT_Buffer_Destroy_Args_STRUCT_SIZE;
    d.buffer = h2d.buffer;
    consume_error(api, api->PJRT_Buffer_Destroy(&d), nullptr, 0);
    return -1;
  }
  std::lock_guard<std::mutex> lock(shim->mu);
  int64_t id = shim->next_buffer_id++;
  DeviceBuf db;
  db.buf = h2d.buffer;
  shim->buffers.emplace(id, db);
  return id;
}

int dl4j_pjrt_buffer_free(void* handle, int64_t buf_id) {
  ShimClient* shim = static_cast<ShimClient*>(handle);
  PJRT_Buffer* to_destroy = nullptr;
  {
    std::lock_guard<std::mutex> lock(shim->mu);
    auto it = shim->buffers.find(buf_id);
    if (it == shim->buffers.end() || it->second.dead) return -1;
    if (it->second.pins > 0) {
      // an execution is using it: destroy deferred to the last unpin
      it->second.dead = true;
      return 0;
    }
    to_destroy = it->second.buf;
    shim->buffers.erase(it);
  }
  destroy_pjrt_buffer(shim->api, to_destroy);
  return 0;
}

namespace {

// Shared execute core.  Each of the executable's num_inputs operands is
// either a persistent device buffer (in_buf_ids[i] >= 1) or the next
// host-staged input (in_buf_ids == nullptr or in_buf_ids[i] < 0); host
// inputs are transferred, used once, and destroyed.
int execute_impl(ShimClient* shim, int64_t exec_id,
                 const int64_t* in_buf_ids, const void* const* host_inputs,
                 const int* host_dtypes, const int* host_ranks,
                 const int64_t* host_dims, int num_inputs,
                 void* const* outputs, const int64_t* out_byte_sizes,
                 int num_outputs, char* err_buf, int err_len) {
  const PJRT_Api* api = shim->api;

  // -- pin the executable and every referenced persistent buffer under
  // -- ONE lock acquisition, so a concurrent buffer_free/cache_clear can
  // -- never destroy them mid-execution (destroy defers to our unpin)
  PJRT_LoadedExecutable* loaded = nullptr;
  size_t expect_outputs = 0;
  std::vector<std::vector<int64_t>> out_dims;
  std::vector<int64_t> pinned_bufs;
  {
    std::lock_guard<std::mutex> lock(shim->mu);
    auto eit = shim->execs.find(exec_id);
    if (eit == shim->execs.end() || eit->second.dead) {
      set_err(err_buf, err_len, "invalid executable id");
      return -1;
    }
    bool ok = true;
    if (in_buf_ids != nullptr) {
      for (int i = 0; i < num_inputs; ++i) {
        if (in_buf_ids[i] < 1) continue;
        auto bit = shim->buffers.find(in_buf_ids[i]);
        if (bit == shim->buffers.end() || bit->second.dead) {
          set_err(err_buf, err_len, "unknown device buffer id");
          ok = false;
          break;
        }
      }
    }
    if (!ok) return -3;
    eit->second.pins++;
    loaded = eit->second.loaded;
    expect_outputs = eit->second.num_outputs;
    out_dims = eit->second.out_dims;
    if (in_buf_ids != nullptr) {
      for (int i = 0; i < num_inputs; ++i) {
        if (in_buf_ids[i] < 1) continue;
        shim->buffers[in_buf_ids[i]].pins++;
        pinned_bufs.push_back(in_buf_ids[i]);
      }
    }
  }

  // from here on, every return path must go through `unpin`
  auto unpin = [&]() {
    std::vector<PJRT_Buffer*> destroy_bufs;
    ExecEntry dead_entry;
    bool have_dead_entry = false;
    {
      std::lock_guard<std::mutex> lock(shim->mu);
      auto eit = shim->execs.find(exec_id);
      if (eit != shim->execs.end()) {
        eit->second.pins--;
        if (eit->second.dead && eit->second.pins == 0) {
          dead_entry = eit->second;
          have_dead_entry = true;
          shim->execs.erase(eit);
        }
      }
      for (int64_t id : pinned_bufs) {
        auto bit = shim->buffers.find(id);
        if (bit == shim->buffers.end()) continue;
        bit->second.pins--;
        if (bit->second.dead && bit->second.pins == 0) {
          destroy_bufs.push_back(bit->second.buf);
          shim->buffers.erase(bit);
        }
      }
    }
    if (have_dead_entry) destroy_exec_entry(shim->api, dead_entry);
    for (PJRT_Buffer* b : destroy_bufs) destroy_pjrt_buffer(shim->api, b);
  };

  if ((size_t)num_outputs != expect_outputs) {
    set_err(err_buf, err_len, "output arity mismatch");
    unpin();
    return -1;
  }
  std::vector<PJRT_Device*> devices = addressable_devices(shim);
  if (devices.empty()) {
    set_err(err_buf, err_len, "no addressable devices");
    unpin();
    return -1;
  }

  // -- assemble the argument list ----------------------------------------
  std::vector<PJRT_Buffer*> arg_buffers((size_t)num_inputs, nullptr);
  std::vector<PJRT_Buffer*> temp_buffers;  // host-staged, destroy after
  int rc = 0;
  int host_cursor = 0;
  const int64_t* dims_cursor = host_dims;
  for (int i = 0; i < num_inputs && rc == 0; ++i) {
    if (in_buf_ids != nullptr && in_buf_ids[i] >= 1) {
      std::lock_guard<std::mutex> lock(shim->mu);
      arg_buffers[(size_t)i] = shim->buffers[in_buf_ids[i]].buf;
      continue;
    }
    PJRT_Client_BufferFromHostBuffer_Args h2d;
    memset(&h2d, 0, sizeof(h2d));
    h2d.struct_size = PJRT_Client_BufferFromHostBuffer_Args_STRUCT_SIZE;
    h2d.client = shim->client;
    h2d.data = host_inputs[host_cursor];
    h2d.type = (PJRT_Buffer_Type)host_dtypes[host_cursor];
    h2d.dims = dims_cursor;
    h2d.num_dims = (size_t)host_ranks[host_cursor];
    dims_cursor += host_ranks[host_cursor];
    ++host_cursor;
    h2d.host_buffer_semantics =
        PJRT_HostBufferSemantics_kImmutableUntilTransferCompletes;
    h2d.device = devices[0];
    PJRT_Error* error = api->PJRT_Client_BufferFromHostBuffer(&h2d);
    if (error != nullptr) {
      consume_error(api, error, err_buf, err_len);
      rc = -3;
      break;
    }
    arg_buffers[(size_t)i] = h2d.buffer;
    temp_buffers.push_back(h2d.buffer);
    if (!await_event(api, h2d.done_with_host_buffer, err_buf, err_len)) {
      rc = -3;
    }
  }

  // -- execute ------------------------------------------------------------
  std::vector<PJRT_Buffer*> out_buffers((size_t)num_outputs, nullptr);
  if (rc == 0) {
    PJRT_ExecuteOptions exec_options;
    memset(&exec_options, 0, sizeof(exec_options));
    exec_options.struct_size = PJRT_ExecuteOptions_STRUCT_SIZE;

    PJRT_Buffer* const* arg_list = arg_buffers.data();
    PJRT_Buffer** output_list = out_buffers.data();
    PJRT_Buffer** const* output_lists = &output_list;
    PJRT_Event* device_complete_event = nullptr;

    PJRT_LoadedExecutable_Execute_Args exec_args;
    memset(&exec_args, 0, sizeof(exec_args));
    exec_args.struct_size = PJRT_LoadedExecutable_Execute_Args_STRUCT_SIZE;
    exec_args.executable = loaded;
    exec_args.options = &exec_options;
    exec_args.argument_lists = &arg_list;
    exec_args.num_devices = 1;
    exec_args.num_args = (size_t)num_inputs;
    exec_args.output_lists = const_cast<PJRT_Buffer***>(output_lists);
    exec_args.device_complete_events = &device_complete_event;
    exec_args.execute_device = devices[0];
    PJRT_Error* error = api->PJRT_LoadedExecutable_Execute(&exec_args);
    if (error != nullptr) {
      consume_error(api, error, err_buf, err_len);
      rc = -4;
    } else if (!await_event(api, device_complete_event, err_buf,
                            err_len)) {
      rc = -4;
    }
  }

  // -- device -> host -----------------------------------------------------
  for (int j = 0; j < num_outputs && rc == 0; ++j) {
    // Ask for dense row-major on the host explicitly: the device buffer
    // keeps whatever layout the compiler picked (TPU outputs are often
    // NOT major-to-minor), and with host_layout == nullptr the copy
    // would come back in that device order.
    size_t rank = out_dims[(size_t)j].size();
    std::vector<int64_t> minor_to_major(rank);
    for (size_t d = 0; d < rank; ++d) {
      minor_to_major[d] = (int64_t)(rank - 1 - d);
    }
    PJRT_Buffer_MemoryLayout layout;
    memset(&layout, 0, sizeof(layout));
    layout.struct_size = PJRT_Buffer_MemoryLayout_STRUCT_SIZE;
    layout.type = PJRT_Buffer_MemoryLayout_Type_Tiled;
    layout.tiled.struct_size = PJRT_Buffer_MemoryLayout_Tiled_STRUCT_SIZE;
    layout.tiled.minor_to_major = minor_to_major.data();
    layout.tiled.minor_to_major_size = rank;

    PJRT_Buffer_ToHostBuffer_Args d2h;
    memset(&d2h, 0, sizeof(d2h));
    d2h.struct_size = PJRT_Buffer_ToHostBuffer_Args_STRUCT_SIZE;
    d2h.src = out_buffers[(size_t)j];
    d2h.host_layout = &layout;
    d2h.dst = outputs[j];
    d2h.dst_size = (size_t)out_byte_sizes[j];
    PJRT_Error* error = api->PJRT_Buffer_ToHostBuffer(&d2h);
    if (error != nullptr) {
      consume_error(api, error, err_buf, err_len);
      rc = -5;
    } else if (!await_event(api, d2h.event, err_buf, err_len)) {
      rc = -5;
    }
  }

  // -- cleanup (persistent buffers + executable stay alive) ---------------
  for (PJRT_Buffer* buf : temp_buffers) {
    PJRT_Buffer_Destroy_Args destroy_buf;
    memset(&destroy_buf, 0, sizeof(destroy_buf));
    destroy_buf.struct_size = PJRT_Buffer_Destroy_Args_STRUCT_SIZE;
    destroy_buf.buffer = buf;
    consume_error(api, api->PJRT_Buffer_Destroy(&destroy_buf), nullptr, 0);
  }
  for (PJRT_Buffer* buf : out_buffers) {
    if (buf == nullptr) continue;
    PJRT_Buffer_Destroy_Args destroy_buf;
    memset(&destroy_buf, 0, sizeof(destroy_buf));
    destroy_buf.struct_size = PJRT_Buffer_Destroy_Args_STRUCT_SIZE;
    destroy_buf.buffer = buf;
    consume_error(api, api->PJRT_Buffer_Destroy(&destroy_buf), nullptr, 0);
  }
  unpin();
  return rc;
}

}  // namespace

// Execute a cached executable with typed, arbitrary-rank host inputs.
// `inputs[i]` is a dense host buffer of dtype code `in_dtypes[i]` with
// rank `in_ranks[i]`; all input dims are concatenated in `in_dims`.
// Outputs are written to the caller-allocated `outputs[j]` buffers
// (sizes in `out_byte_sizes`, query via dl4j_pjrt_exec_output_info).
// Returns 0 on success.
int dl4j_pjrt_execute(void* handle, int64_t exec_id,
                      const void* const* inputs, const int* in_dtypes,
                      const int* in_ranks, const int64_t* in_dims,
                      int num_inputs, void* const* outputs,
                      const int64_t* out_byte_sizes, int num_outputs,
                      char* err_buf, int err_len) {
  ShimClient* shim = static_cast<ShimClient*>(handle);
  return execute_impl(shim, exec_id, nullptr, inputs, in_dtypes, in_ranks,
                      in_dims, num_inputs, outputs, out_byte_sizes,
                      num_outputs, err_buf, err_len);
}

// Execute with a mix of persistent device buffers (in_buf_ids[i] >= 1)
// and host-staged inputs (in_buf_ids[i] < 0 consumes the next entry of
// the host_* arrays, in order).
int dl4j_pjrt_execute_mixed(void* handle, int64_t exec_id,
                            const int64_t* in_buf_ids,
                            const void* const* host_inputs,
                            const int* host_dtypes, const int* host_ranks,
                            const int64_t* host_dims, int num_inputs,
                            void* const* outputs,
                            const int64_t* out_byte_sizes, int num_outputs,
                            char* err_buf, int err_len) {
  ShimClient* shim = static_cast<ShimClient*>(handle);
  return execute_impl(shim, exec_id, in_buf_ids, host_inputs, host_dtypes,
                      host_ranks, host_dims, num_inputs, outputs,
                      out_byte_sizes, num_outputs, err_buf, err_len);
}

// Back-compat single-output f32 rank-1 entry point, now riding the
// executable cache (repeat calls with the same program skip compilation).
int dl4j_pjrt_run_mlir(void* handle, const char* mlir_code,
                       const char* compile_options,
                       int64_t compile_options_size,
                       const float* const* inputs, int num_inputs,
                       int64_t n, float* output, int64_t out_n,
                       char* err_buf, int err_len) {
  int64_t exec_id = dl4j_pjrt_compile_cached(
      handle, mlir_code, compile_options, compile_options_size, nullptr,
      err_buf, err_len);
  if (exec_id < 0) return -2;
  int num_outputs = dl4j_pjrt_exec_num_outputs(handle, exec_id);
  if (num_outputs != 1) {
    set_err(err_buf, err_len,
            "dl4j_pjrt_run_mlir supports single-output programs only "
            "(use dl4j_pjrt_execute)");
    return -2;
  }
  int f32 = dl4j_pjrt_dtype_code("f32");
  std::vector<const void*> ins(inputs, inputs + num_inputs);
  std::vector<int> dtypes((size_t)num_inputs, f32);
  std::vector<int> ranks((size_t)num_inputs, 1);
  std::vector<int64_t> dims((size_t)num_inputs, n);
  void* outs[1] = {output};
  int64_t out_bytes[1] = {out_n * (int64_t)sizeof(float)};
  return dl4j_pjrt_execute(handle, exec_id, ins.data(), dtypes.data(),
                           ranks.data(), dims.data(), num_inputs, outs,
                           out_bytes, 1, err_buf, err_len);
}

}  // extern "C"
