// Minimal mock PJRT plugin for hermetic tests of the native predictor.
//
// The image ships no CPU PJRT plugin .so (jaxlib links its CPU client
// statically; only libtpu exports GetPjrtApi), so CI
// cannot run real XLA through the C API without hardware. This mock
// implements exactly the call surface `csrc/predictor.cc` uses and
// executes every program as the IDENTITY function (output i = input i),
// which is enough to prove the runner's artifact loading, buffer
// marshaling, execute sequencing, and error handling end-to-end through
// a real PJRT_Api dispatch table. Numeric parity against XLA is covered
// by the TPU-gated test with the real plugin.
//
// The analog in the reference's test strategy: `ps_local_client.cc`, the
// in-process degenerate PS backend used where the brpc service would be.

#include <cstdint>
#include <cstring>
#include <new>
#include <string>
#include <vector>

#include "xla/pjrt/c/pjrt_c_api.h"

namespace {

struct MockError {
  std::string message;
};

struct MockBuffer {
  PJRT_Buffer_Type type;
  std::vector<int64_t> dims;
  std::vector<char> data;
};

struct MockExecutable {
  size_t num_args = 0;
};

struct MockClient {
  int device_tag = 0;  // &device_tag doubles as the PJRT_Device*
};

PJRT_Error* err(const std::string& m) {
  return reinterpret_cast<PJRT_Error*>(new MockError{m});
}

size_t type_bytes(PJRT_Buffer_Type t) {
  switch (t) {
    case PJRT_Buffer_Type_PRED:
    case PJRT_Buffer_Type_S8:
    case PJRT_Buffer_Type_U8:
      return 1;
    case PJRT_Buffer_Type_S16:
    case PJRT_Buffer_Type_U16:
    case PJRT_Buffer_Type_F16:
    case PJRT_Buffer_Type_BF16:
      return 2;
    case PJRT_Buffer_Type_S64:
    case PJRT_Buffer_Type_U64:
    case PJRT_Buffer_Type_F64:
      return 8;
    default:
      return 4;
  }
}

// ---- error ----
void Error_Destroy(PJRT_Error_Destroy_Args* a) {
  delete const_cast<MockError*>(
      reinterpret_cast<const MockError*>(a->error));
}

void Error_Message(PJRT_Error_Message_Args* a) {
  const auto* e = reinterpret_cast<const MockError*>(a->error);
  a->message = e->message.c_str();
  a->message_size = e->message.size();
}

PJRT_Error* Error_GetCode(PJRT_Error_GetCode_Args* a) {
  a->code = PJRT_Error_Code_INTERNAL;
  return nullptr;
}

// ---- client ----
PJRT_Error* Client_Create(PJRT_Client_Create_Args* a) {
  a->client = reinterpret_cast<PJRT_Client*>(new MockClient());
  return nullptr;
}

PJRT_Error* Client_Destroy(PJRT_Client_Destroy_Args* a) {
  delete reinterpret_cast<MockClient*>(a->client);
  return nullptr;
}

PJRT_Error* Client_PlatformName(PJRT_Client_PlatformName_Args* a) {
  static const char kName[] = "mock";
  a->platform_name = kName;
  a->platform_name_size = 4;
  return nullptr;
}

PJRT_Error* Client_AddressableDevices(
    PJRT_Client_AddressableDevices_Args* a) {
  auto* c = reinterpret_cast<MockClient*>(a->client);
  static thread_local PJRT_Device* dev;
  dev = reinterpret_cast<PJRT_Device*>(&c->device_tag);
  a->addressable_devices = &dev;
  a->num_addressable_devices = 1;
  return nullptr;
}

PJRT_Error* Client_Compile(PJRT_Client_Compile_Args* a) {
  std::string code(a->program->code, a->program->code_size);
  if (code.rfind("MOCK-IDENTITY", 0) != 0) {
    return err("mock plugin only compiles MOCK-IDENTITY programs (got " +
               code.substr(0, 24) + "...)");
  }
  a->executable =
      reinterpret_cast<PJRT_LoadedExecutable*>(new MockExecutable());
  return nullptr;
}

// ---- buffers ----
PJRT_Error* BufferFromHostBuffer(
    PJRT_Client_BufferFromHostBuffer_Args* a) {
  auto* b = new MockBuffer();
  b->type = a->type;
  b->dims.assign(a->dims, a->dims + a->num_dims);
  size_t n = type_bytes(a->type);
  for (size_t i = 0; i < a->num_dims; ++i) n *= (size_t)a->dims[i];
  b->data.resize(n);
  std::memcpy(b->data.data(), a->data, n);
  a->buffer = reinterpret_cast<PJRT_Buffer*>(b);
  a->done_with_host_buffer = nullptr;  // copied synchronously
  return nullptr;
}

PJRT_Error* Buffer_Destroy(PJRT_Buffer_Destroy_Args* a) {
  delete reinterpret_cast<MockBuffer*>(a->buffer);
  return nullptr;
}

PJRT_Error* Buffer_ToHostBuffer(PJRT_Buffer_ToHostBuffer_Args* a) {
  auto* b = reinterpret_cast<MockBuffer*>(a->src);
  if (!a->dst) {
    a->dst_size = b->data.size();
    return nullptr;
  }
  if (a->dst_size < b->data.size()) return err("dst too small");
  std::memcpy(a->dst, b->data.data(), b->data.size());
  a->event = nullptr;  // synchronous copy
  return nullptr;
}

// ---- executable ----
PJRT_Error* LoadedExecutable_Destroy(
    PJRT_LoadedExecutable_Destroy_Args* a) {
  delete reinterpret_cast<MockExecutable*>(a->executable);
  return nullptr;
}

PJRT_Error* LoadedExecutable_GetExecutable(
    PJRT_LoadedExecutable_GetExecutable_Args* a) {
  a->executable =
      reinterpret_cast<PJRT_Executable*>(a->loaded_executable);
  return nullptr;
}

PJRT_Error* Executable_NumOutputs(PJRT_Executable_NumOutputs_Args* a) {
  // identity: #outputs == #args of the last Execute; unknown before the
  // first run — report 0 ("unknown"), the runner falls back to its sig
  a->num_outputs = 0;
  return nullptr;
}

PJRT_Error* LoadedExecutable_Execute(
    PJRT_LoadedExecutable_Execute_Args* a) {
  if (a->num_devices != 1) return err("mock is single-device");
  for (size_t i = 0; i < a->num_args; ++i) {
    auto* in = reinterpret_cast<MockBuffer*>(a->argument_lists[0][i]);
    auto* out = new MockBuffer(*in);  // identity
    a->output_lists[0][i] = reinterpret_cast<PJRT_Buffer*>(out);
  }
  if (a->device_complete_events) a->device_complete_events[0] = nullptr;
  return nullptr;
}

// ---- events (all mock ops are synchronous; events are null) ----
PJRT_Error* Event_Destroy(PJRT_Event_Destroy_Args*) { return nullptr; }
PJRT_Error* Event_Await(PJRT_Event_Await_Args*) { return nullptr; }

}  // namespace

extern "C" const PJRT_Api* GetPjrtApi() {
  static PJRT_Api api;
  static bool init = false;
  if (!init) {
    std::memset(&api, 0, sizeof(api));
    api.struct_size = PJRT_Api_STRUCT_SIZE;
    api.pjrt_api_version.struct_size = PJRT_Api_Version_STRUCT_SIZE;
    api.pjrt_api_version.major_version = PJRT_API_MAJOR;
    api.pjrt_api_version.minor_version = PJRT_API_MINOR;
    api.PJRT_Error_Destroy = Error_Destroy;
    api.PJRT_Error_Message = Error_Message;
    api.PJRT_Error_GetCode = Error_GetCode;
    api.PJRT_Client_Create = Client_Create;
    api.PJRT_Client_Destroy = Client_Destroy;
    api.PJRT_Client_PlatformName = Client_PlatformName;
    api.PJRT_Client_AddressableDevices = Client_AddressableDevices;
    api.PJRT_Client_Compile = Client_Compile;
    api.PJRT_Client_BufferFromHostBuffer = BufferFromHostBuffer;
    api.PJRT_Buffer_Destroy = Buffer_Destroy;
    api.PJRT_Buffer_ToHostBuffer = Buffer_ToHostBuffer;
    api.PJRT_LoadedExecutable_Destroy = LoadedExecutable_Destroy;
    api.PJRT_LoadedExecutable_GetExecutable =
        LoadedExecutable_GetExecutable;
    api.PJRT_Executable_NumOutputs = Executable_NumOutputs;
    api.PJRT_LoadedExecutable_Execute = LoadedExecutable_Execute;
    api.PJRT_Event_Destroy = Event_Destroy;
    api.PJRT_Event_Await = Event_Await;
    init = true;
  }
  return &api;
}
