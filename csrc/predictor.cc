// Native serving runner over the PJRT C API.
//
// Reference surface: the standalone C++ inference engine —
// `paddle/fluid/inference/api/analysis_predictor.cc:973` (ZeroCopyRun)
// and its C ABI `paddle/fluid/inference/capi_exp/pd_inference_api.h`.
// The reference loads a Program proto and runs it through NaiveExecutor
// with per-op kernels; the TPU-native shape is radically smaller: the
// exported artifact IS a compiled-format program (StableHLO bytecode
// written by `paddle_tpu.inference.save_inference_model`), and the whole
// execution engine is whatever PJRT plugin the caller points us at
// (libtpu on TPU hosts; any CPU PJRT plugin
// elsewhere). No Python is linked, imported, or embedded here.
//
// Artifact layout (written by save_inference_model):
//   <path>.mlir — StableHLO module bytecode (portable; params baked in)
//   <path>.sig  — text signature: "input|output <name> <dtype> <dims>"
//
// C ABI (ZeroCopy style: caller owns every host buffer):
//   ptp_create(artifact, plugin, err, errlen) -> handle
//   ptp_num_inputs/outputs, ptp_io_rank/shape/dtype
//   ptp_run(handle, in_ptrs[], out_ptrs[], err, errlen)
//   ptp_destroy(handle)

#include <dlfcn.h>

#include <cstdint>
#include <cstdio>
#include <cstring>
#include <fstream>
#include <sstream>
#include <string>
#include <vector>

#include "xla/pjrt/c/pjrt_c_api.h"

namespace {

struct IoSpec {
  std::string name;
  std::string dtype;       // our stable code: f32, bf16, s32, ...
  std::vector<int64_t> dims;
};

struct Predictor {
  void* plugin_handle = nullptr;
  const PJRT_Api* api = nullptr;
  PJRT_Client* client = nullptr;
  PJRT_LoadedExecutable* exec = nullptr;
  PJRT_Device* device = nullptr;
  std::vector<IoSpec> inputs, outputs;
  size_t num_exec_outputs = 0;
};

void set_err(char* err, int errlen, const std::string& msg) {
  if (err && errlen > 0) {
    std::snprintf(err, (size_t)errlen, "%s", msg.c_str());
  }
}

// Returns empty string on success, else the PJRT error message.
std::string take_error(const PJRT_Api* api, PJRT_Error* e) {
  if (!e) return "";
  PJRT_Error_Message_Args ma;
  std::memset(&ma, 0, sizeof(ma));
  ma.struct_size = PJRT_Error_Message_Args_STRUCT_SIZE;
  ma.error = e;
  api->PJRT_Error_Message(&ma);
  std::string msg(ma.message, ma.message_size);
  PJRT_Error_Destroy_Args da;
  std::memset(&da, 0, sizeof(da));
  da.struct_size = PJRT_Error_Destroy_Args_STRUCT_SIZE;
  da.error = e;
  api->PJRT_Error_Destroy(&da);
  return msg;
}

std::string await_event(const PJRT_Api* api, PJRT_Event* ev) {
  if (!ev) return "";
  PJRT_Event_Await_Args aa;
  std::memset(&aa, 0, sizeof(aa));
  aa.struct_size = PJRT_Event_Await_Args_STRUCT_SIZE;
  aa.event = ev;
  std::string msg = take_error(api, api->PJRT_Event_Await(&aa));
  PJRT_Event_Destroy_Args ed;
  std::memset(&ed, 0, sizeof(ed));
  ed.struct_size = PJRT_Event_Destroy_Args_STRUCT_SIZE;
  ed.event = ev;
  api->PJRT_Event_Destroy(&ed);
  return msg;
}

struct DtypeInfo {
  const char* code;
  PJRT_Buffer_Type type;
  size_t bytes;
};

const DtypeInfo kDtypes[] = {
    {"f32", PJRT_Buffer_Type_F32, 4},  {"f64", PJRT_Buffer_Type_F64, 8},
    {"f16", PJRT_Buffer_Type_F16, 2},  {"bf16", PJRT_Buffer_Type_BF16, 2},
    {"s8", PJRT_Buffer_Type_S8, 1},    {"s16", PJRT_Buffer_Type_S16, 2},
    {"s32", PJRT_Buffer_Type_S32, 4},  {"s64", PJRT_Buffer_Type_S64, 8},
    {"u8", PJRT_Buffer_Type_U8, 1},    {"u16", PJRT_Buffer_Type_U16, 2},
    {"u32", PJRT_Buffer_Type_U32, 4},  {"u64", PJRT_Buffer_Type_U64, 8},
    {"pred", PJRT_Buffer_Type_PRED, 1},
};

const DtypeInfo* dtype_info(const std::string& code) {
  for (const auto& d : kDtypes) {
    if (code == d.code) return &d;
  }
  return nullptr;
}

size_t elem_count(const IoSpec& s) {
  size_t n = 1;
  for (int64_t d : s.dims) n *= (size_t)d;
  return n;
}

bool parse_sig(const std::string& path, Predictor* p, std::string* err) {
  std::ifstream f(path);
  if (!f) {
    *err = "cannot open signature file " + path;
    return false;
  }
  std::string line;
  while (std::getline(f, line)) {
    if (line.empty() || line[0] == '#') continue;
    std::istringstream is(line);
    std::string kind, name, dtype, dims;
    is >> kind >> name >> dtype >> dims;
    if (kind == "version") continue;
    if (kind != "input" && kind != "output") {
      *err = "bad signature line: " + line;
      return false;
    }
    IoSpec spec;
    spec.name = name;
    spec.dtype = dtype;
    if (!dtype_info(dtype)) {
      *err = "unsupported dtype in signature: " + dtype;
      return false;
    }
    if (dims != "scalar") {
      std::istringstream ds(dims);
      std::string tok;
      while (std::getline(ds, tok, ',')) {
        long long v = atoll(tok.c_str());
        if (v < 0) {
          *err = "dynamic dim in " + name +
                 ": the native runner serves static shapes only — "
                 "re-export without symbolic dims";
          return false;
        }
        spec.dims.push_back((int64_t)v);
      }
    }
    (kind == "input" ? p->inputs : p->outputs).push_back(std::move(spec));
  }
  if (p->outputs.empty()) {
    *err = "signature lists no outputs";
    return false;
  }
  return true;
}

}  // namespace

extern "C" {

void ptp_destroy(void* h);  // forward: used by ptp_create error paths

void* ptp_create(const char* artifact_path, const char* plugin_path,
                 char* err, int errlen) {
  auto* p = new Predictor();
  std::string msg;
  std::string base(artifact_path);

  // 1. artifact
  std::ifstream mf(base + ".mlir", std::ios::binary);
  if (!mf) {
    set_err(err, errlen,
            "cannot open " + base + ".mlir (native serving needs the "
            ".mlir artifact written by save_inference_model)");
    delete p;
    return nullptr;
  }
  std::string code((std::istreambuf_iterator<char>(mf)),
                   std::istreambuf_iterator<char>());
  if (!parse_sig(base + ".sig", p, &msg)) {
    set_err(err, errlen, msg);
    delete p;
    return nullptr;
  }

  // 2. plugin
  p->plugin_handle = dlopen(plugin_path, RTLD_NOW | RTLD_LOCAL);
  if (!p->plugin_handle) {
    set_err(err, errlen, std::string("dlopen plugin: ") + dlerror());
    delete p;
    return nullptr;
  }
  auto get_api = (const PJRT_Api* (*)())dlsym(p->plugin_handle,
                                              "GetPjrtApi");
  if (!get_api) {
    set_err(err, errlen, "plugin has no GetPjrtApi symbol");
    delete p;
    return nullptr;
  }
  p->api = get_api();

  // 3. client + device
  {
    PJRT_Client_Create_Args ca;
    std::memset(&ca, 0, sizeof(ca));
    ca.struct_size = PJRT_Client_Create_Args_STRUCT_SIZE;
    msg = take_error(p->api, p->api->PJRT_Client_Create(&ca));
    if (!msg.empty()) {
      set_err(err, errlen, "PJRT_Client_Create: " + msg);
      delete p;
      return nullptr;
    }
    p->client = ca.client;
  }
  {
    PJRT_Client_AddressableDevices_Args da;
    std::memset(&da, 0, sizeof(da));
    da.struct_size = PJRT_Client_AddressableDevices_Args_STRUCT_SIZE;
    da.client = p->client;
    msg = take_error(p->api, p->api->PJRT_Client_AddressableDevices(&da));
    if (!msg.empty() || da.num_addressable_devices == 0) {
      set_err(err, errlen, "no addressable devices: " + msg);
      ptp_destroy(p);
      return nullptr;
    }
    p->device = da.addressable_devices[0];
  }

  // 4. compile. Options = hand-encoded CompileOptionsProto (we link no
  // protobuf): field 3 (executable_build_options) submessage with
  // num_replicas=1 (field 4) and num_partitions=1 (field 5).
  {
    static const char kCompileOptions[] = {0x1A, 0x04, 0x20, 0x01,
                                           0x28, 0x01};
    PJRT_Program prog;
    std::memset(&prog, 0, sizeof(prog));
    prog.struct_size = PJRT_Program_STRUCT_SIZE;
    prog.code = code.data();
    prog.code_size = code.size();
    prog.format = "mlir";
    prog.format_size = 4;
    PJRT_Client_Compile_Args ca;
    std::memset(&ca, 0, sizeof(ca));
    ca.struct_size = PJRT_Client_Compile_Args_STRUCT_SIZE;
    ca.client = p->client;
    ca.program = &prog;
    ca.compile_options = kCompileOptions;
    ca.compile_options_size = sizeof(kCompileOptions);
    msg = take_error(p->api, p->api->PJRT_Client_Compile(&ca));
    if (!msg.empty()) {
      set_err(err, errlen, "PJRT_Client_Compile: " + msg);
      ptp_destroy(p);
      return nullptr;
    }
    p->exec = ca.executable;
  }

  // 5. output arity check against the signature
  {
    PJRT_LoadedExecutable_GetExecutable_Args ga;
    std::memset(&ga, 0, sizeof(ga));
    ga.struct_size = PJRT_LoadedExecutable_GetExecutable_Args_STRUCT_SIZE;
    ga.loaded_executable = p->exec;
    msg = take_error(p->api,
                     p->api->PJRT_LoadedExecutable_GetExecutable(&ga));
    if (msg.empty()) {
      PJRT_Executable_NumOutputs_Args na;
      std::memset(&na, 0, sizeof(na));
      na.struct_size = PJRT_Executable_NumOutputs_Args_STRUCT_SIZE;
      na.executable = ga.executable;
      msg = take_error(p->api, p->api->PJRT_Executable_NumOutputs(&na));
      if (msg.empty()) p->num_exec_outputs = na.num_outputs;
      if (p->api->PJRT_Executable_Destroy) {
        PJRT_Executable_Destroy_Args xa;
        std::memset(&xa, 0, sizeof(xa));
        xa.struct_size = PJRT_Executable_Destroy_Args_STRUCT_SIZE;
        xa.executable = ga.executable;
        take_error(p->api, p->api->PJRT_Executable_Destroy(&xa));
      }
    }
    if (p->num_exec_outputs == 0) {
      p->num_exec_outputs = p->outputs.size();
    } else if (p->num_exec_outputs != p->outputs.size()) {
      set_err(err, errlen,
              "signature/executable output count mismatch");
      ptp_destroy(p);
      return nullptr;
    }
  }
  return p;
}

int ptp_num_inputs(void* h) {
  return (int)static_cast<Predictor*>(h)->inputs.size();
}

int ptp_num_outputs(void* h) {
  return (int)static_cast<Predictor*>(h)->outputs.size();
}

static const IoSpec* io_spec(void* h, int is_input, int i) {
  auto* p = static_cast<Predictor*>(h);
  const auto& v = is_input ? p->inputs : p->outputs;
  if (i < 0 || (size_t)i >= v.size()) return nullptr;
  return &v[i];
}

int ptp_io_rank(void* h, int is_input, int i) {
  const IoSpec* s = io_spec(h, is_input, i);
  return s ? (int)s->dims.size() : -1;
}

void ptp_io_shape(void* h, int is_input, int i, int64_t* dims) {
  const IoSpec* s = io_spec(h, is_input, i);
  if (s) std::memcpy(dims, s->dims.data(), s->dims.size() * 8);
}

// returns the dtype code string (static storage)
const char* ptp_io_dtype(void* h, int is_input, int i) {
  const IoSpec* s = io_spec(h, is_input, i);
  return s ? dtype_info(s->dtype)->code : "";
}

int64_t ptp_io_bytes(void* h, int is_input, int i) {
  const IoSpec* s = io_spec(h, is_input, i);
  if (!s) return -1;
  return (int64_t)(elem_count(*s) * dtype_info(s->dtype)->bytes);
}

int ptp_run(void* h, const void** in_bufs, void** out_bufs, char* err,
            int errlen) {
  auto* p = static_cast<Predictor*>(h);
  const PJRT_Api* api = p->api;
  std::string msg;
  std::vector<PJRT_Buffer*> dev_in(p->inputs.size(), nullptr);
  std::vector<PJRT_Buffer*> dev_out(p->num_exec_outputs, nullptr);
  int rc = 0;

  // H2D: synchronous-copy semantics (ImmutableOnlyDuringCall) keeps the
  // ZeroCopyRun contract simple — the caller may reuse its input buffers
  // the moment ptp_run returns.
  for (size_t i = 0; i < p->inputs.size() && rc == 0; ++i) {
    const IoSpec& s = p->inputs[i];
    PJRT_Client_BufferFromHostBuffer_Args ba;
    std::memset(&ba, 0, sizeof(ba));
    ba.struct_size = PJRT_Client_BufferFromHostBuffer_Args_STRUCT_SIZE;
    ba.client = p->client;
    ba.data = in_bufs[i];
    ba.type = dtype_info(s.dtype)->type;
    ba.dims = s.dims.data();
    ba.num_dims = s.dims.size();
    ba.host_buffer_semantics =
        PJRT_HostBufferSemantics_kImmutableOnlyDuringCall;
    ba.device = p->device;
    msg = take_error(api, api->PJRT_Client_BufferFromHostBuffer(&ba));
    if (!msg.empty()) {
      set_err(err, errlen, "H2D input " + s.name + ": " + msg);
      rc = -1;
      break;
    }
    dev_in[i] = ba.buffer;
    msg = await_event(api, ba.done_with_host_buffer);
    if (!msg.empty()) {
      set_err(err, errlen, "H2D await " + s.name + ": " + msg);
      rc = -1;
    }
  }

  // execute
  if (rc == 0) {
    PJRT_ExecuteOptions opts;
    std::memset(&opts, 0, sizeof(opts));
    opts.struct_size = PJRT_ExecuteOptions_STRUCT_SIZE;
    PJRT_Buffer* const* arg_list = dev_in.data();
    PJRT_Buffer** out_list = dev_out.data();
    PJRT_Event* done = nullptr;
    PJRT_LoadedExecutable_Execute_Args ea;
    std::memset(&ea, 0, sizeof(ea));
    ea.struct_size = PJRT_LoadedExecutable_Execute_Args_STRUCT_SIZE;
    ea.executable = p->exec;
    ea.options = &opts;
    ea.argument_lists = &arg_list;
    ea.num_devices = 1;
    ea.num_args = dev_in.size();
    ea.output_lists = &out_list;
    ea.device_complete_events = &done;
    msg = take_error(api, api->PJRT_LoadedExecutable_Execute(&ea));
    if (!msg.empty()) {
      set_err(err, errlen, "Execute: " + msg);
      rc = -2;
    } else {
      msg = await_event(api, done);
      if (!msg.empty()) {
        set_err(err, errlen, "Execute await: " + msg);
        rc = -2;
      }
    }
  }

  // D2H into caller buffers
  for (size_t i = 0; i < p->outputs.size() && rc == 0; ++i) {
    const IoSpec& s = p->outputs[i];
    PJRT_Buffer_ToHostBuffer_Args ta;
    std::memset(&ta, 0, sizeof(ta));
    ta.struct_size = PJRT_Buffer_ToHostBuffer_Args_STRUCT_SIZE;
    ta.src = dev_out[i];
    ta.dst = out_bufs[i];
    ta.dst_size = elem_count(s) * dtype_info(s.dtype)->bytes;
    msg = take_error(api, api->PJRT_Buffer_ToHostBuffer(&ta));
    if (!msg.empty()) {
      set_err(err, errlen, "D2H output " + s.name + ": " + msg);
      rc = -3;
      break;
    }
    msg = await_event(api, ta.event);
    if (!msg.empty()) {
      set_err(err, errlen, "D2H await " + s.name + ": " + msg);
      rc = -3;
    }
  }

  for (PJRT_Buffer* b : dev_in) {
    if (!b) continue;
    PJRT_Buffer_Destroy_Args da;
    std::memset(&da, 0, sizeof(da));
    da.struct_size = PJRT_Buffer_Destroy_Args_STRUCT_SIZE;
    da.buffer = b;
    take_error(api, api->PJRT_Buffer_Destroy(&da));
  }
  for (PJRT_Buffer* b : dev_out) {
    if (!b) continue;
    PJRT_Buffer_Destroy_Args da;
    std::memset(&da, 0, sizeof(da));
    da.struct_size = PJRT_Buffer_Destroy_Args_STRUCT_SIZE;
    da.buffer = b;
    take_error(api, api->PJRT_Buffer_Destroy(&da));
  }
  return rc;
}

void ptp_destroy(void* h) {
  auto* p = static_cast<Predictor*>(h);
  if (!p) return;
  if (p->api) {
    if (p->exec) {
      PJRT_LoadedExecutable_Destroy_Args ea;
      std::memset(&ea, 0, sizeof(ea));
      ea.struct_size = PJRT_LoadedExecutable_Destroy_Args_STRUCT_SIZE;
      ea.executable = p->exec;
      take_error(p->api, p->api->PJRT_LoadedExecutable_Destroy(&ea));
    }
    if (p->client) {
      PJRT_Client_Destroy_Args ca;
      std::memset(&ca, 0, sizeof(ca));
      ca.struct_size = PJRT_Client_Destroy_Args_STRUCT_SIZE;
      ca.client = p->client;
      take_error(p->api, p->api->PJRT_Client_Destroy(&ca));
    }
  }
  // NOTE: the plugin stays dlopen'd for the process lifetime — PJRT
  // plugins do not support unload.
  delete p;
}

}  // extern "C"
